"""Cuts a real trace of a program that names its spans and kernels down to
something small enough to keep beside ``v5e_train_step.xplane.pb``:

    python3 benchmark/testdata/cut_named_trace.py train <in.xplane.pb> <out>
    python3 benchmark/testdata/cut_named_trace.py serve <in.xplane.pb> <out>

``train`` keeps one step: from the first ``bench.step_call`` to the end of
the first ``bench.block``. ``serve`` keeps a few decode rounds around the
first admission that has a whole round before it and two after: from the
start of the last ``pt.serve.decode_round`` before that ``pt.serve.admit``
to the end of the second one after it.
Kept: the first device plane's ``XLA Ops`` events that lie wholly in the cut,
the host's ``bench.*`` and ``pt.*`` spans that overlap it (clipped to it),
and a ``bench.window`` span that is the cut itself, so the readers find
their window. The serve programs are 36 unrolled layers and two of them run
in the cut, so every op loses its operands, and one that is not a Mosaic
kernel its number too (``%fusion = bf16[...] fusion()``: a few dozen names,
not three thousand); the readers look only at a kernel's name and at
intervals.
Run by hand (needs TensorFlow's copy of the xplane schema).
"""
import re
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

MOSAIC = 'custom_call_target="tpu_custom_call"'


def shorten(name: str) -> str:
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    m = re.search(r" ([a-z][a-z0-9\-]*)\(", rest)
    if not m:
        return name
    out_type, op = rest[:m.start()], m.group(1)
    if MOSAIC in name:
        return f"{head} = {out_type} {op}(), {MOSAIC}"
    return f"{re.sub(r'[.0-9]+$', '', head)} = {out_type} {op}()"


def spans_of(plane):
    """(start_ps, end_ps, name) of every event of the host's lines."""
    out = []
    for ln in plane.lines:
        for e in ln.events:
            t = ln.timestamp_ns * 1000 + e.offset_ps
            out.append((t, t + e.duration_ps,
                        plane.event_metadata[e.metadata_id].name))
    return sorted(out)


def cut_of(kind, spans):
    if kind == "train":
        lo = next(s for s in spans if s[2] == "bench.step_call")[0]
        hi = next(s for s in spans if s[2] == "bench.block")[1]
        return lo, hi
    rounds = [s for s in spans if s[2] == "pt.serve.decode_round"]
    for admit in (s for s in spans if s[2] == "pt.serve.admit"):
        before = [r for r in rounds if r[1] <= admit[0]]
        after = [r for r in rounds if r[0] >= admit[1]]
        if before and len(after) >= 2:
            return before[-1][0], after[1][1]
    raise SystemExit("no admission with a whole round before and two after")


def main(kind, src, dst):
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    host = next(p for p in space.planes if p.name == "/host:CPU")
    lo, hi = cut_of(kind, spans_of(host))
    out = xplane_pb2.XSpace()

    names = {}

    def add(new, line, name, t0, t1):
        ids = names.setdefault(new.name, {})
        if name not in ids:
            ids[name] = len(ids) + 1
            md = new.event_metadata[ids[name]]
            md.id, md.name = ids[name], name
        line.events.add(metadata_id=ids[name], offset_ps=t0 - lo,
                        duration_ps=t1 - t0)

    new = out.planes.add(id=host.id, name=host.name)
    line = new.lines.add(id=1, name="python3", timestamp_ns=lo // 1000)
    add(new, line, "bench.window", lo, hi)
    for t0, t1, name in spans_of(host):
        if name.startswith(("bench.", "pt.")) and name != "bench.window" \
                and t0 < hi and t1 > lo:
            add(new, line, name, max(t0, lo), min(t1, hi))
    dev = next(p for p in space.planes if p.name == "/device:TPU:0")
    new = out.planes.add(id=dev.id, name=dev.name)
    for ln in dev.lines:
        if ln.name != "XLA Ops":
            continue
        line = new.lines.add(id=ln.id, name=ln.name,
                             timestamp_ns=lo // 1000)
        for e in ln.events:
            t0 = ln.timestamp_ns * 1000 + e.offset_ps
            if lo <= t0 and t0 + e.duration_ps <= hi:
                name = dev.event_metadata[e.metadata_id].name
                add(new, line, shorten(name) if kind == "serve" else name,
                    t0, t0 + e.duration_ps)
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print("kept", sum(len(ln.events) for p in out.planes for ln in p.lines),
          "events in", len(out.SerializeToString()), "bytes; cut",
          (hi - lo) / 1e12, "s")


if __name__ == "__main__":
    main(*sys.argv[1:4])
