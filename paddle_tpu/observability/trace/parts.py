"""The parts of a served model step: ONE vocabulary of ``jax.named_scope``
names, set where the work is asked for (the engine's ``attend`` closures and
the served blocks) and read back from a device trace.

A scope is metadata: XLA carries a jaxpr's name stack into every
instruction's ``op_name``, and the profiler writes it beside every device
event (the ``tf_op`` stat of the event's metadata). It changes no
instruction, costs nothing on the device, and nothing per call once a
program is built. An op belongs to the INNERMOST ``pt.<part>`` of its name
stack (a norm inside a projection group is ``norm``), a fusion to what the
compiler says of it (its root's), and an op the compiler inserted with no
name at all (a layout copy) to the op that consumes it — the readers'
rule: ``observability.trace.xplane.correlate().by_part``,
``tools/program_parts.py``, and the benchmark's
``serve.part_<part>_share_pct``.

A SUBPART is a second, nested vocabulary (``SUBPARTS``; ``pt.indexer``: the
index projections, scores and top-k of a learned sparse attention;
``pt.retention``: the kernel calls of a power retention layer and their glue,
inside ``attention``; ``pt.mhc``: the residual path of a model whose stream is
several rows — a sublayer's mixing maps and the mix itself, inside
``attn_proj`` and ``mlp``): it marks work INSIDE parts without being one. The ten parts stay a partition of the
step — a reader of ``PARTS`` skips a ``pt.`` name outside its vocabulary and
finds the part around it, so the indexer's projections are still ``attn_proj``
and its scores ``attention`` — and one reader of its own
(``serve.indexer_share_pct``) asks what the subpart costs across them.
"""
from __future__ import annotations

import functools

__all__ = ["PARTS", "SUBPARTS", "PREFIX", "part", "subpart", "part_of"]

PARTS = ("embed", "norm", "attn_proj", "cache_write", "attention", "mlp",
         "router", "experts", "mixer", "head")
SUBPARTS = ("indexer", "retention", "mhc")
PREFIX = "pt."


class part:
    """``with part("norm"): ...`` or ``@part("norm")``: the work traced
    inside is named ``pt.norm``. A name outside ``PARTS`` raises."""

    _names, _what = PARTS, "a part of a model step"

    def __init__(self, name: str):
        if name not in self._names:
            raise ValueError(
                f"{name!r} is not {self._what}: {self._names}")
        self.name = name
        self._scope = None

    def __enter__(self):
        import jax

        self._scope = jax.named_scope(PREFIX + self.name)
        return self._scope.__enter__()

    def __exit__(self, *exc):
        scope, self._scope = self._scope, None
        return scope.__exit__(*exc)

    def __call__(self, fn):
        name = self.name

        cls = type(self)

        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with cls(name):
                return fn(*args, **kwargs)

        return scoped


class subpart(part):
    """``part``'s twin for the nested vocabulary ``SUBPARTS``: used INSIDE a
    part (``@part("attn_proj")`` above ``@subpart("indexer")``)."""

    _names, _what = SUBPARTS, "a subpart of a model step"


def part_of(name_stack: str):
    """The innermost ``pt.<part>`` of an op's name stack (``jit(pt_window1)/
    pt.attn_proj/pt.norm/mul`` -> ``norm``); ``None`` where it holds none."""
    for seg in reversed(name_stack.split("/")):
        if seg.startswith(PREFIX) and seg[len(PREFIX):] in PARTS:
            return seg[len(PREFIX):]
    return None
