"""An outline of a raw trace for reading by hand (``--dump-trace <dir>``):
planes, lines, event counts, the names that take most time with their stats.
Not part of any metric."""
import json
import os
import shutil
from collections import defaultdict

from . import xplane


def dump(tracer, out_dir: str, keep_pb_under: int = 24 << 20) -> None:
    os.makedirs(out_dir, exist_ok=True)
    if tracer.xplane is None:
        return
    planes = xplane.read_xplane(tracer.xplane)
    outline = {}
    for pname, lines in planes.items():
        outline[pname] = {}
        for lname, evs in lines.items():
            by = defaultdict(lambda: [0, 0.0])
            for n, s, e in evs:
                by[n][0] += 1
                by[n][1] += e - s
            top = sorted(by.items(), key=lambda kv: -kv[1][1])[:40]
            outline[pname][lname] = {
                "events": len(evs),
                "first_ns": min((s for _n, s, _e in evs), default=None),
                "last_ns": max((e for _n, _s, e in evs), default=None),
                "top": [[n, c, t] for n, (c, t) in top]}
    dev = [p for p in planes if xplane.DEVICE_PLANE.match(p)]
    stats = {}
    if dev:
        stats = xplane.read_event_stats(tracer.xplane, dev[0],
                                        xplane.OPS_LINE, limit=400)
    with open(os.path.join(out_dir, "trace_outline.json"), "w") as f:
        json.dump({"file": tracer.xplane,
                   "bytes": os.path.getsize(tracer.xplane),
                   "outline": outline, "ops_stats": stats,
                   "summary": tracer.summary}, f, indent=1, default=str)
    if os.path.getsize(tracer.xplane) <= keep_pb_under:
        shutil.copy(tracer.xplane, os.path.join(out_dir, "trace.xplane.pb"))
