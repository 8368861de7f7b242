"""The parts of a model step: ONE vocabulary of ``jax.named_scope`` names, set
where the work is asked for (the engine's ``attend`` closures and the served
blocks; the layers of ``models/llama.py`` for a train step) and read back
from a device trace.

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
``serve.part_<part>_share_pct`` / ``train.part_<part>_share_pct``.

A SUBPART is a second, nested vocabulary (``SUBPARTS``; ``pt.indexer``: the
index projections, scores and top-k of a learned sparse attention;
``pt.retention``: the kernel calls of a power retention layer and their glue,
inside ``attention``; ``pt.mhc``: the residual path of a model whose stream is
several rows — a sublayer's mixing maps and the mix itself, inside
``attn_proj`` and ``mlp``; ``pt.ssm_scan``: a Mamba-2 layer's recurrence —
the chunked scan of a prefill, the one-step kernel of a round — inside
``mixer``; ``pt.cca_mix``: what a compressed convolutional attention does to
its latent queries and keys between the projections and the cache — the two
causal convolutions, the q-k mean, the L2 norm and temperature, the partial
RoPE — inside ``attn_proj``): it marks work INSIDE parts without being one.
The ten parts stay a partition of the
step — a reader of ``PARTS`` skips a ``pt.`` name outside its vocabulary and
finds the part around it, so the indexer's projections are still ``attn_proj``
and its scores ``attention`` — and one reader of its own
(``serve.indexer_share_pct``) asks what the subpart costs across them.

A STEP PART (``STEP_PARTS``) names what a train step holds AROUND the model:
``pt.stack`` around a stacked run's ``lax.scan`` (``StackedStageRun``), so
that the scan's own plumbing — the ``dynamic_update_slice`` that writes a
kept value's layer into its stack, the ``dynamic_slice`` that reads it back,
the carries — has a name while every model op inside keeps its innermost
part; ``pt.optimizer`` around the update (``jit.make_param_updater``). A
reader of a train step hands ``PARTS + STEP_PARTS`` (``part_of``'s default);
a reader of a served program hands ``PARTS`` alone and so skips both, as it
skips a subpart.

The PHASE of an op of a train step is in the same string (``phase_of``): JAX
wraps the name stack of what it differentiates. Forward ops read
``jit(step)/jvp(pt.stack)/while/body/closed_call/pt.mlp/dot_general`` (the
scope INSIDE the wrapper where the differentiated function opens it:
``jvp(pt.head)/mul``), backward ops ``.../transpose(jvp(pt.stack))/while/
body/closed_call/checkpoint/pt.mlp/dot_general``, and what a recompute
replays ``.../checkpoint/rematted_computation/pt.mlp/tanh``. Those three
spellings are jax's, not this module's: ``tests/test_train_step_parts.py``
compiles a tiny step and fails loudly when a jax renames one.
"""
from __future__ import annotations

import functools
import re

__all__ = ["PARTS", "SUBPARTS", "STEP_PARTS", "PHASES", "PREFIX", "part",
           "subpart", "step_part", "part_of", "phase_of"]

PARTS = ("embed", "norm", "attn_proj", "cache_write", "attention", "mlp",
         "router", "experts", "mixer", "head")
SUBPARTS = ("indexer", "retention", "mhc", "ssm_scan", "cca_mix")
STEP_PARTS = ("stack", "optimizer")
PHASES = ("forward", "recompute", "backward")
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


class step_part(part):
    """``part``'s twin for ``STEP_PARTS``: what a train step holds around
    the model (``with step_part("stack"):`` around a stack's scan)."""

    _names, _what = STEP_PARTS, "a part of a train step around the model"


# a transform wraps the segment it differentiates: ``jvp(pt.head)``,
# ``transpose(jvp(pt.stack))``
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()*" + re.escape(PREFIX)
                      + r"(\w+)\)*$")


def part_of(name_stack: str, names=PARTS + STEP_PARTS):
    """The innermost ``pt.<part>`` of an op's name stack (``jit(pt_window1)/
    pt.attn_proj/pt.norm/mul`` -> ``norm``), looked for inside a transform's
    wrapper too (``jit(step)/jvp(pt.head)/mul`` -> ``head``); ``None`` where
    it holds none of ``names``."""
    for seg in reversed(name_stack.split("/")):
        m = _WRAPPED.match(seg)
        if m and m.group(1) in names:
            return m.group(1)
    return None


def phase_of(name_stack: str):
    """Which pass of a differentiated step asked for an op: ``recompute``
    where the stack holds ``rematted_computation``, else ``backward`` where
    a segment starts ``transpose(``, else ``forward`` where one starts
    ``jvp(``, else ``None`` (the optimizer, anything outside the
    gradient)."""
    segs = name_stack.split("/")
    if "rematted_computation" in segs:
        return "recompute"
    if any(seg.startswith("transpose(") for seg in segs):
        return "backward"
    if any(seg.startswith("jvp(") for seg in segs):
        return "forward"
    return None
