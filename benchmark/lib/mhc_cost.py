"""What the residual path of manifold-constrained hyper-connections
(``paddle_tpu/kernels/pallas/mhc.py``: a token's stream is ``n`` rows of
``C``, and every sublayer computes three mixing maps from the whole stream,
reads a mix of the rows and writes them back mixed) has to move and compute
for one (token, sublayer) MIX — what the MATHEMATICS must move, whatever
implements it, the benchmark's own arithmetic kept apart from the program's:

- bytes: the stream read once and written once, ``2 x n x C x 4`` (float32),
  plus the sublayer's input out and its output in, ``2 x C x 4``. An
  implementation that reads the stream twice (``mhc_pre``, then ``mhc_post``)
  moves more and reads under 100 %; one that fused a sublayer's post-mix into
  the next one's pre-mix would read against the same work;
- operations: the skinny projection ``2 n C (2n + n^2)`` and the two mixes
  ``2 n C (n + 2)`` (``u`` from ``n`` rows; ``n`` rows each from ``n`` rows
  and the output); the Sinkhorn iterations on ``n^2`` values are left out: a
  floor that can only be too low.

At ``n`` 4, ``C`` 3584 a mix is 143 360 B against 860 160 operations: 0.175 us
by the bytes at 819 GB/s, 0.0044 us by the operations at 197 TFLOP/s — bound
by memory 40 to 1.
"""
from typing import Dict, Iterable, Optional, Tuple

from . import program_trace, xplane
from .mla_cost import floor_seconds  # noqa: F401  (the same two bounds)


def mix_cost(mixes: int, shape: Dict) -> Dict:
    """``mixes`` (token, sublayer) pairs of a stream of ``shape["streams"]``
    rows of ``shape["hidden"]``."""
    n, c, item = shape["streams"], shape["hidden"], shape["itemsize"]
    return {"bytes": mixes * (2 * n * c * item + 2 * c * item),
            "flops": mixes * (2 * n * c * (2 * n + n * n)
                              + 2 * n * c * (n + 2))}


def scope_ns(devices: Iterable, name: str, lo: float, hi: float
             ) -> Optional[Tuple[float, float]]:
    """``(self time, busy time)`` in ``[lo, hi)``, ns, of the ops whose own
    scope is ``name`` — kernel or not — over ``devices`` (each ``(ops,
    runs)`` as ``part_time.read_devices`` gives them with the nested
    vocabulary); ``None`` where no op has the scope."""
    took = busy = 0.0
    for ops, _runs in devices:
        ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        clipped = program_trace.clip(
            [(i, s, e) for i, (_p, s, e) in enumerate(ops)], lo, hi)
        busy += xplane.total(xplane.union((s, e) for _i, s, e in clipped))
        took += sum(e - s for i, s, e in xplane.leaf_segments(clipped)
                    if ops[i][0] == name)
    return (took, busy) if took and busy else None


def traced_scope_ns(shapes: Dict, name: str):
    """``scope_ns`` of the run's own trace (``None``: no trace, no window, a
    program without the nested vocabulary or without the scope)."""
    from . import harness, part_time

    pt = program_trace.current(shapes, "serve")
    if pt is None or pt.window is None:
        return None
    try:
        from paddle_tpu.observability.trace.parts import SUBPARTS
    except ImportError:       # a program without the nested vocabulary
        return None
    if name not in SUBPARTS:  # the parent of the PR that added the scope
        return None
    path = program_trace.find_run_xplane(harness.ROOT,
                                         program_trace.process_start())
    if path is None:
        return None
    try:
        devices = part_time.read_devices(path, SUBPARTS)
    except (ValueError, IndexError):    # not the schema part_time reads
        return None
    return scope_ns(devices, name, *pt.window)
