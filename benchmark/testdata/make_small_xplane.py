"""Writes ``small.xplane.pb``: a trace in the profiler's own format (XSpace)
with the structure a v5e trace has — two device planes with an ``XLA Ops``
line (events named by their whole HLO text, nested ops, a collective, a
Mosaic call; an ``Async XLA Ops`` line that must not count as busy) and a host plane with the
benchmark's spans — small enough to work the answers out by hand
(``tests/bench/test_bench_arithmetic.py`` holds them). Run by hand; needs
TensorFlow's copy of the xplane schema, which the tests do not."""
import os

from tensorflow.tsl.profiler.protobuf import xplane_pb2

HERE = os.path.dirname(os.path.abspath(__file__))


_NAMES = {}


def add_line(plane, name, events):
    names = _NAMES.setdefault(plane.id, {})  # event metadata is per plane
    line = plane.lines.add(id=len(plane.lines) + 1, name=name,
                           timestamp_ns=0)
    for ev_name, start_ns, end_ns, stats in events:
        if ev_name not in names:
            mid = len(names) + 1
            names[ev_name] = mid
            plane.event_metadata[mid].id = mid
            plane.event_metadata[mid].name = ev_name
        ev = line.events.add(metadata_id=names[ev_name],
                             offset_ps=start_ns * 1000,
                             duration_ps=(end_ns - start_ns) * 1000)
        for k, v in stats.items():
            sid = None
            for i, m in plane.stat_metadata.items():
                if m.name == k:
                    sid = i
            if sid is None:
                sid = len(plane.stat_metadata) + 1
                plane.stat_metadata[sid].id = sid
                plane.stat_metadata[sid].name = k
            ev.stats.add(metadata_id=sid, str_value=v)


def main():
    space = xplane_pb2.XSpace()
    host = space.planes.add(id=1, name="/host:CPU")
    add_line(host, "python3", [
        ("bench.window", 1000, 11000, {}),
        ("bench.step_call", 1000, 1400, {}),
        ("bench.next_batch", 1400, 3000, {}),
        ("bench.block", 3000, 11000, {}),
        ("PjitFunction(step)", 1000, 1400, {}),
    ])
    T = "bf16[4,8]{1,0:T(8,128)(2,1)}"
    WHILE = (f"%while.1 = (s32[]{{:T(128)}}, {T}) while((s32[]{{:T(128)}}, "
             f"{T}) %tuple.1), condition=%cond.1, body=%body.1")
    F1 = (f"%fusion.1 = {T} fusion({T} %param.1), kind=kOutput, "
          f"calls=%fused_computation.1")
    AR = (f"%all-reduce.3 = {T} all-reduce({T} %fusion.1), channel_id=1, "
          f"replica_groups={{{{0,1}}}}, to_apply=%add.1")
    KERNEL = (f"%_unknown_.7 = {T} custom-call({T} %all-reduce.3), "
              f"custom_call_target=\"tpu_custom_call\", "
              f"frontend_attributes={{kernel_metadata={{}}}}")
    F2 = (f"%fusion.2 = {T} fusion({T} %_unknown_.7), kind=kLoop, "
          f"calls=%fused_computation.2")
    F9 = (f"%fusion.9 = {T} fusion({T} %while.1), kind=kLoop, "
          f"calls=%fused_computation.9")
    d0 = space.planes.add(id=2, name="/device:TPU:0")
    add_line(d0, "XLA Modules", [("jit_step(1)", 2000, 10000, {})])
    add_line(d0, "XLA Ops", [
        (WHILE, 2000, 9000, {}), (F1, 2000, 4000, {}), (AR, 4000, 5000, {}),
        (KERNEL, 5000, 6000, {}), (F2, 6500, 8500, {}),
        (F9, 9500, 10000, {}),
    ])
    add_line(d0, "Async XLA Ops", [
        (f"%copy-start.1 = ({T}, {T}, u32[]{{:S(2)}}) copy-start({T} "
         f"%param.2)", 1500, 9800, {}),
    ])
    d1 = space.planes.add(id=3, name="/device:TPU:1")
    add_line(d1, "XLA Ops", [
        (F1, 2000, 4000, {}), (AR, 4000, 5500, {}), (F2, 5500, 9000, {}),
    ])
    with open(os.path.join(HERE, "small.xplane.pb"), "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    main()
