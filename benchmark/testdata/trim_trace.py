"""Cuts a real trace down to something small enough to keep: the first
device plane's ``XLA Ops`` events of ONE ``bench.window``'s first
``bench.block`` (one train step) and the benchmark's host spans around it.
Run by hand (needs TensorFlow's copy of the xplane schema):

    python3 benchmark/testdata/trim_trace.py <in.xplane.pb> <out.xplane.pb>
"""
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2


def main(src, dst):
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    host = next(p for p in space.planes if p.name == "/host:CPU")
    py = next(ln for ln in host.lines if ln.name == "python3")
    spans = sorted((py.timestamp_ns * 1000 + e.offset_ps, e.duration_ps,
                    host.event_metadata[e.metadata_id].name)
                   for e in py.events
                   if host.event_metadata[e.metadata_id].name.startswith(
                       "bench."))
    first_call = next(s for s in spans if s[2] == "bench.step_call")
    blocks = [s for s in spans if s[2] == "bench.block"]
    lo, hi = first_call[0], blocks[0][0] + blocks[0][1]

    def keep(plane, line_names, pred):
        new = out.planes.add(id=plane.id, name=plane.name)
        for ln in plane.lines:
            if ln.name not in line_names:
                continue
            nl = new.lines.add(id=ln.id, name=ln.name,
                               timestamp_ns=ln.timestamp_ns)
            for e in ln.events:
                t = ln.timestamp_ns * 1000 + e.offset_ps
                name = plane.event_metadata[e.metadata_id].name
                if lo <= t and t + e.duration_ps <= hi and pred(name):
                    nl.events.add(metadata_id=e.metadata_id,
                                  offset_ps=e.offset_ps,
                                  duration_ps=e.duration_ps)
                    md = new.event_metadata[e.metadata_id]
                    md.id, md.name = e.metadata_id, name

    keep(host, {"python3"},
         lambda n: n.startswith("bench.") and n != "bench.window")
    dev = next(p for p in space.planes if p.name == "/device:TPU:0")
    keep(dev, {"XLA Ops"}, lambda n: True)
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print("kept", sum(len(ln.events) for p in out.planes for ln in p.lines),
          "events in", len(out.SerializeToString()), "bytes; window",
          (hi - lo) / 1e12, "s")


if __name__ == "__main__":
    main(*sys.argv[1:3])
