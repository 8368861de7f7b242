"""Cuts a real trace of a program that names the parts of its step down to
something small enough to keep, WITH the stats a part is read from
(``trim_trace.py`` and ``cut_named_trace.py`` drop every stat; their files
stay as they are):

    python3 benchmark/testdata/trim_trace_with_stats.py <in.xplane.pb> <out>

Kept: the first device plane's ``XLA Modules`` events of the shortest stretch
of two consecutive window-program runs (``jit_pt_*``) that holds a carrying
call and a call of another program (a smaller bucket's, in a trace of
``laguna-xs2-d5.mixed-context-peak``) and the ``XLA Ops`` events inside it;
of an event's metadata its name — operands and layouts dropped, the number
kept: ``%fusion.12 = bf16[640,2048] fusion()`` — and the stats ``tf_op`` (the
jaxpr name stack, a string kept once a plane where ops share it) and
``program_id``; on the host the ``pt.*`` spans that overlap the cut, clipped
to it, and a ``bench.window`` span that is the cut itself, so the readers
find their window.
Run by hand (needs TensorFlow's copy of the xplane schema).
"""
import re
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

MOSAIC = 'custom_call_target="tpu_custom_call"'
KEPT_STATS = ("tf_op", "program_id")


def shorten(name: str) -> str:
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    m = re.search(r" ([a-z][a-z0-9\-]*)\(", rest)
    if not m:
        return name
    out_type = re.sub(r"\{[^}]*\}", "", rest[:m.start()])
    tail = f", {MOSAIC}" if MOSAIC in name else ""
    return f"{head} = {out_type} {m.group(1)}(){tail}"


def main(src, dst):
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    dev = next(p for p in space.planes if p.name == "/device:TPU:0")
    line = {ln.name: ln for ln in dev.lines}
    mods = sorted((ln.timestamp_ns * 1000 + e.offset_ps, e.duration_ps,
                   dev.event_metadata[e.metadata_id].name)
                  for ln in [line["XLA Modules"]] for e in ln.events)
    # the shortest stretch of consecutive window-program runs that holds a
    # carrying call and a call of another program
    runs = [m for m in mods if m[2].startswith("jit_pt_")]
    best = None
    for a, b in zip(runs, runs[1:]):
        names = {re.sub(r"\(\d+\)$", "", m[2]) for m in (a, b)}
        if len(names) == 2 and any(n.endswith("_carry") for n in names):
            lo, hi = a[0], b[0] + b[1]
            if best is None or hi - lo < best[1] - best[0]:
                best = (lo, hi)
    lo, hi = best
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
          len(out.SerializeToString()), "bytes; window", (hi - lo) / 1e9,
          "ms;", sorted({m[2] for m in mods if lo <= m[0] < hi}))


if __name__ == "__main__":
    main(*sys.argv[1:3])
