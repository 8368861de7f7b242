"""Cuts a real trace of a TRAIN cell whose program names the parts of its step
down to ONE step, WITH the stats a part and a phase are read from
(``trim_trace_with_stats.py``'s twin: that one looks for two window programs
of a serve cell and finds none here; its file stays as it is):

    python3 benchmark/testdata/trim_train_trace_with_stats.py <in.xplane.pb> <out>

Kept: of the first device plane the middle ``jit_step`` run of the ``XLA
Modules`` line and the ``XLA Ops`` events inside it; of an event's metadata
its name, shortened as ``trim_trace_with_stats.shorten`` does (operands and
layouts dropped, the number and a Mosaic call's target kept), and the stats
``tf_op`` (the jaxpr name stack: part and phase; a string kept once a plane)
and ``program_id``; on the host the ``pt.*`` spans that overlap the cut,
clipped to it, and a ``bench.window`` span that is the cut itself, so the
readers find their window.
Run by hand (needs TensorFlow's copy of the xplane schema).
"""
import os
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from trim_trace_with_stats import KEPT_STATS, shorten  # noqa: E402


def main(src, dst):
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    dev = next(p for p in space.planes if p.name == "/device:TPU:0")
    line = {ln.name: ln for ln in dev.lines}
    steps = sorted((ln.timestamp_ns * 1000 + e.offset_ps, e.duration_ps)
                   for ln in [line["XLA Modules"]] for e in ln.events
                   if dev.event_metadata[e.metadata_id].name.startswith(
                       "jit_step"))
    lo, dur = steps[len(steps) // 2]
    hi = lo + dur
    out = xplane_pb2.XSpace()

    new = out.planes.add(id=dev.id, name=dev.name)
    stat_id = {}                     # name of a stat or a shared string -> id

    def sid(name):
        if name not in stat_id:
            stat_id[name] = len(stat_id) + 1
            new.stat_metadata[stat_id[name]].id = stat_id[name]
            new.stat_metadata[stat_id[name]].name = name
        return stat_id[name]

    names = {k: v.name for k, v in dev.stat_metadata.items()}
    for lname in ("XLA Modules", "XLA Ops"):
        ln = line[lname]
        nl = new.lines.add(id=ln.id, name=ln.name,
                           timestamp_ns=ln.timestamp_ns)
        for e in ln.events:
            t = ln.timestamp_ns * 1000 + e.offset_ps
            if not (lo <= t and t + e.duration_ps <= hi):
                continue
            nl.events.add(metadata_id=e.metadata_id, offset_ps=e.offset_ps,
                          duration_ps=e.duration_ps)
            if e.metadata_id in new.event_metadata:
                continue
            old = dev.event_metadata[e.metadata_id]
            md = new.event_metadata[e.metadata_id]
            md.id, md.name = e.metadata_id, shorten(old.name)
            for st in old.stats:
                if names.get(st.metadata_id) not in KEPT_STATS:
                    continue
                ns = md.stats.add(metadata_id=sid(names[st.metadata_id]))
                if st.str_value or st.ref_value:
                    ns.ref_value = sid(st.str_value
                                       or names[st.ref_value])
                else:
                    ns.uint64_value = st.uint64_value or st.int64_value

    host = next(p for p in space.planes if p.name == "/host:CPU")
    nh = out.planes.add(id=host.id, name=host.name)
    nl = nh.lines.add(id=1, name="python3", timestamp_ns=0)
    ids = {}

    def put(name, s, e):
        if name not in ids:
            ids[name] = len(ids) + 1
            nh.event_metadata[ids[name]].id = ids[name]
            nh.event_metadata[ids[name]].name = name
        nl.events.add(metadata_id=ids[name], offset_ps=int(s),
                      duration_ps=int(e - s))

    put("bench.window", lo, hi)
    for ln in host.lines:
        for e in ln.events:
            name = host.event_metadata[e.metadata_id].name
            s = ln.timestamp_ns * 1000 + e.offset_ps
            if name.startswith("pt.") and s < hi and s + e.duration_ps > lo:
                put(name, max(s, lo), min(s + e.duration_ps, hi))
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print("kept", sum(len(ln.events) for p in out.planes for ln in p.lines),
          "events,", len(new.event_metadata), "instructions,",
          len(out.SerializeToString()), "bytes; one step of", len(steps),
          "in the trace,", (hi - lo) / 1e9, "ms")


if __name__ == "__main__":
    main(*sys.argv[1:3])
