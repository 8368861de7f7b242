"""Generic compiled pipeline execution for homogeneous layer runs.

Reference: fleet/meta_parallel/pipeline_parallel.py:80 (forward_backward_pipeline,
the 1F1B schedule driving ANY PipelineLayer) + pp_layers.py:132. The reference
executes each stage in its own process and exchanges activations over NCCL p2p.

TPU-native mapping: a contiguous run of structurally identical layers (the
transformer blocks of a GPT/BERT/Llama/DiT) has its parameters stacked on a
leading stage dim sharded over 'pp'; ONE compiled program runs the microbatch
pipeline with lax.ppermute stage handoffs (see pipeline.py). Heterogeneous
edge layers (embedding, head, final norm) execute outside the run under plain
GSPMD — they are cheap and their params are placed by their own specs. This is
the same schedule 1F1B produces, expressed as a compiler-visible scan: autodiff
of the tick scan IS the cooldown pipeline, and jax.checkpoint around the stage
body bounds live activations to O(microbatch) exactly like early-backward.
"""
from __future__ import annotations

import contextlib
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...core.dispatch import primitive
from ...core.tensor import Tensor
from ...nn.layer.layers import Layer, Parameter
from ...observability.trace.parts import step_part
from ..mesh import get_mesh_env
from .mp_layers import MP_OUT

_RUN_REGISTRY = {}

# streamed-offload trace mode (jit.StreamedTrainStep): stacked params arrive
# as TPU pinned-host arrays and the stack unrolls layer-by-layer H2D copies
# instead of scanning device-resident weights
_STREAM_MODE = [False]
# segmented-offload hook (jit/offload_stream.SegmentedTrainStep): when set,
# StackedStageRun.forward delegates to handler(run, hidden) so the step can
# hand-schedule the per-layer forward/backward walk
_SEG_HANDLER = [None]


def _memory_sharding(kind: str):
    """SingleDeviceSharding with a memory kind; None when the backend cannot
    execute memory-space placement (the CPU test backend lists pinned_host
    but has no annotate_device_placement kernel — and everything is host RAM
    there anyway)."""
    from jax.sharding import SingleDeviceSharding

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    try:
        kinds = {m.kind for m in dev.addressable_memories()}
    except Exception:
        kinds = set()
    if kind not in kinds:
        return None
    return SingleDeviceSharding(dev, memory_kind=kind)


# The projection outputs a layer may name where it produces them
# (``name_for_recompute``; models/llama.py does): q and k after RoPE, v, a
# row-parallel attention output no ``mp`` names, and the MLP's up / gate.
# A name is inert until a recompute's policy keeps it; WHICH of them the
# default policy keeps is chosen against the compiled step's memory
# (jit/remat_fit.py) and reaches the stack as ``keep``.
ATTN_Q, ATTN_K, ATTN_V, ATTN_O = "attn_q", "attn_k", "attn_v", "attn_o"
MLP_UP, MLP_GATE = "mlp_up", "mlp_gate"

_KEEP = [()]


@contextlib.contextmanager
def keeping(names):
    """The step traced inside keeps ``names`` through its stacks' recompute,
    besides what every policy keeps (``StackedStageRun.forward`` reads it
    and hands it to the stack's primitive as an attribute)."""
    prior, _KEEP[0] = _KEEP[0], tuple(names)
    try:
        yield
    finally:
        _KEEP[0] = prior


def name_for_recompute(t: Tensor, name: str) -> Tensor:
    """``t`` under ``name`` for a recompute's policy, where ``t`` is traced
    with the tape off — as a stack's body runs its template, and a compiled
    step its model. The name lowers to nothing: a program no policy reads it
    in is the program without it, to the letter. Anywhere else there is no
    recompute to read it and ``t`` comes back as it is."""
    from ...core import autograd

    if autograd.is_grad_enabled() or not isinstance(t.data, jax.core.Tracer):
        return t
    return Tensor(checkpoint_name(t.data, name),
                  stop_gradient=t.stop_gradient)


def remat_wrap(fn, keep=()):
    """jax.checkpoint with the policy chosen by FLAGS_remat_policy. Every
    policy keeps two things by name. What crossed ``mp`` (a row-parallel
    layer's summed output, ``mp_layers.MP_OUT``: 2 x batch x seq x hidden
    bytes a layer, ÷ ``mp`` where the stream lies sequence-sharded and the
    sum is a reduce-scatter; off a mesh with ``mp`` nothing carries the name
    and nothing is kept). And what the flash-attention forward kernel produced
    (``flash_o`` / ``flash_lse``, the residuals its backward reads, named in
    ``kernels/flash_attention.py`` by the dense entry: 2 x batch x seq x
    hidden bytes + 4 x batch x heads x seq a layer; where attention takes
    the XLA softmax or the ring, nothing carries the names), so the replayed
    layer never runs that kernel again. Beyond those:
    '' = what fits: ``keep``, the projection outputs (the names above) that
    ``jit/remat_fit.py`` found the compiled step has memory for — none of
    them (full remat: save the inputs, recompute everything else) where the
    device states no memory limit, as on the CPU, or nothing chose;
    'dots' = save dot/matmul outputs without batch dims (skip re-running the
    MXU work in backward at the cost of activation HBM — the reference's
    selective-recompute tier), 'dots_all' = save every matmul output,
    'flash' = full remat (a value kept accepted), 'moe'/'route' =
    pin the named MoE buffers/routing maps (names exist only on the default 'index' dispatch
    path — under sort/einsum/gmm these two degrade to full remat).
    The unscanned layer list (``distributed/utils_recompute.py``: a plain
    ``jax.checkpoint``, no policy) keeps nothing by name."""
    try:
        from ...framework import flags as flags_mod

        pol = flags_mod.get_flags("FLAGS_remat_policy")["FLAGS_remat_policy"]
    except Exception:
        pol = ""
    policies = jax.checkpoint_policies
    # 'moe': the expert capacity buffer + expert outputs (named in
    # nn/layer/moe.py); the backward recompute then rebuilds only the g/u
    # projections from the saved buffer instead of re-running routing +
    # dispatch + down-proj.
    # 'route': ONLY the routing decisions (slot/keep/src maps + gates,
    # ~1MB/layer): the backward recompute replays the expert matmuls but skips
    # the router matmul/softmax/top_k/cumsum/int-scatter chain — near-zero
    # memory for the routing chain's time
    names = {"": tuple(keep),
             "moe": ("moe_buf", "moe_out", "moe_route"),
             "route": ("moe_route",)}.get(pol, ())
    policy = policies.save_only_these_names(MP_OUT, "flash_o", "flash_lse",
                                            *names)
    dots = {"dots": policies.dots_with_no_batch_dims_saveable,
            "dots_all": policies.dots_saveable}.get(pol)
    if dots is not None:
        policy = policies.save_from_both_policies(dots, policy)
    return jax.checkpoint(fn, policy=policy)


def layer_signature(layer: Layer):
    """Structural identity: same class + same named param shapes/dtypes means
    two layers can share one stacked stage body."""
    params = tuple((n, tuple(p.shape), str(p.dtype))
                   for n, p in sorted(layer.named_parameters()))
    if not params:
        return None  # param-less layers (activations) are never stacked
    return (type(layer).__qualname__, params)


def _row_groups(carry):
    """The scan body's rows as TWO independent groups, or None for one.

    Where ``mp`` splits the stream's sequence (``mesh.activation_spec``'s
    ``"rows"``: ``mp`` > 1 divides it) every sublayer boundary is a
    collective over ``mp`` — a reduce-scatter behind each row-parallel
    matmul, an all-gather in front of each column-parallel one — and a
    decoder layer is one serial chain, so each of them would hold the chip
    with nothing to run beside it. Two halves of the rows are two chains:
    the scheduler puts one half's all-gather under the other's matmuls
    (this chip's compiler makes an all-gather asynchronous, never an
    all-reduce: PERF.md section 7). Taken where a data replica (``dp`` x
    ``sdp``) holds an even number of rows; each half keeps rows of EVERY
    replica (``[D, 2, b/2D, ...]``, index the second dim), so no row
    changes device. Not under ``pp`` (a stage already walks microbatches and
    its kernels are the jnp references), nor for another rank than
    [batch, seq, hidden]. Off such a mesh nothing of this traces.

    The same mathematics but one rounding: a weight's gradient becomes
    ``dW(half 0) + dW(half 1)``, one more add in the parameter's dtype than
    one contraction over all rows — the order every gradient-accumulating
    step already has."""
    from .pipeline import _batch_shard_degree

    env = get_mesh_env()
    if env is None or carry.ndim != 3:
        return None
    mp, d = env.get_dim("mp"), _batch_shard_degree(env)
    b, s = carry.shape[:2]
    if mp == 1 or env.get_dim("pp") > 1 or s % mp or b % (2 * d):
        return None
    rows = carry.reshape(d, 2, b // (2 * d), *carry.shape[1:])
    return [rows[:, i].reshape(b // 2, *carry.shape[1:]) for i in range(2)]


def _join_rows(halves, shape):
    """``_row_groups``'s inverse: the two halves back in the rows' order."""
    from .pipeline import _batch_shard_degree

    d = _batch_shard_degree(get_mesh_env())
    return jnp.stack([h.reshape(d, -1, *shape[1:]) for h in halves],
                     axis=1).reshape(shape)


class StackedStageRun(Layer):
    """A run of structurally identical layers executed as a stacked scan —
    pipelined over 'pp' when the mesh has that axis, plain lax.scan otherwise.

    Takes ALREADY-BUILT layers (each independently initialized so the stacked
    init matches building them separately); keeps layers[0] as the traced
    template and re-registers the stacked arrays as this Layer's Parameters.

    The scan's body runs the template once on the carry — or, where ``mp``
    splits the stream's sequence and a data replica holds an even number of
    rows, once on each HALF of every replica's rows (``_row_groups``): two
    independent chains in one body, so one half's all-gather over ``mp``
    runs under the other half's matmuls. Chosen from the mesh and the
    carry's shape alone; a layer that reports an auxiliary loss (a router:
    it couples its rows) sees them whole.
    """

    def __init__(self, layers: List[Layer], num_microbatches: Optional[int] = None,
                 recompute: bool = False):
        super().__init__()
        if not layers:
            raise ValueError("StackedStageRun needs at least one layer")
        sig = layer_signature(layers[0])
        if sig is None or any(layer_signature(l) != sig for l in layers[1:]):
            raise ValueError("layers are not structurally identical")
        self.depth = len(layers)
        self.num_microbatches = num_microbatches
        self.recompute = recompute
        self._template = [layers[0]]  # list-wrapped: hidden from sublayers
        env = get_mesh_env()
        pp = env.get_dim("pp") if env is not None else 1
        from jax.sharding import PartitionSpec as P

        self._names = []
        self._slice_shapes = []  # true per-layer shapes (streamed offload may
        #                          re-pack the host buffers into aligned slabs)
        per_layer = [dict(l.named_parameters()) for l in layers]
        for name, p in layers[0].named_parameters():
            self._slice_shapes.append(tuple(p.shape))
            stacked = Parameter(jnp.stack([pl[name].data for pl in per_layer]))
            base = tuple(p.dist_spec) if p.dist_spec is not None else (None,) * p.ndim
            stacked.dist_spec = P(*((("pp" if pp > 1 else None),) + base))
            stacked.stop_gradient = p.stop_gradient
            safe = name.replace(".", "__")
            self.add_parameter(safe, stacked)
            self._names.append((safe, name))
        # free the duplicate per-layer arrays (the stacked copy is canonical;
        # layer 0 stays intact as the template's mutation slots). Every
        # per-layer param is marked so an optimizer that captured them BEFORE
        # stacking (wrong fleet order: optimizer before distributed_model)
        # fails loudly instead of silently training dead buffers.
        for l in layers[1:]:
            for n, p in l.named_parameters():
                p.data = jnp.zeros((0,), p.data.dtype)
                p._stacked_into = self
        for n, p in layers[0].named_parameters():
            p._stacked_into = self
        _RUN_REGISTRY[id(self)] = self

    def forward(self, hidden):
        if _SEG_HANDLER[0] is not None:
            from ...core.tensor import Tensor

            out = _SEG_HANDLER[0](self, hidden.data
                                  if isinstance(hidden, Tensor) else hidden)
            return Tensor(out) if not isinstance(out, Tensor) else out
        stacked = [self._parameters[safe] for safe, _ in self._names]
        # the run's own plumbing (the scan's carries, a kept value's write
        # into its stack and its read back, the pipeline's handoffs) is
        # ``stack``; a model op inside keeps its innermost part
        with step_part("stack"):
            out, aux = _run_stack(
                hidden, *stacked, _run_id=id(self),
                use_recompute=self.recompute and self.training,
                microbatches=self.num_microbatches or 0,
                stream=_STREAM_MODE[0], keep=_KEEP[0])
        from ...nn.layer import moe as moe_mod

        moe_mod.record_aux(aux)
        return out


@primitive("pp_stage_stack")
def _run_stack_fn(hidden, *stacked, _run_id, use_recompute, microbatches,
                  stream=False, keep=()):
    from ...core import autograd
    from ...nn.layer import moe as moe_mod

    run = _RUN_REGISTRY[_run_id]
    template = run._template[0]
    tparams = [dict(template.named_parameters())[orig] for _, orig in run._names]

    def run_template(rows):
        with moe_mod.collect_aux() as bucket, autograd.no_grad():
            return template(Tensor(rows)).data, bucket

    def body(carry, slices):
        saved = [p.data for p in tparams]
        try:
            for p, s in zip(tparams, slices):
                p.data = s
            groups = _row_groups(carry)
            out, bucket = run_template(groups[0] if groups else carry)
            if groups and bucket:
                # the layer reported an auxiliary loss (a router's): it
                # couples its rows, so it sees them whole
                out, bucket = run_template(carry)
            elif groups:
                out = _join_rows([out, run_template(groups[1])[0]],
                                 carry.shape)
        finally:
            for p, a in zip(tparams, saved):
                p.data = a
        aux = sum((t.data for t in bucket), jnp.zeros((), jnp.float32))
        return out, aux

    env = get_mesh_env()
    pp = env.get_dim("pp") if env is not None else 1
    if stream:
        # streamed ZeRO-offload (reference sharding_stage3.py:50 offload +
        # TaskFlow prefetch :737): the stacked weights live in TPU pinned
        # host memory; each layer's slice is copied into HBM right before
        # use (XLA emits async copy-start/done, overlapping the previous
        # layer's compute), and index_in_dim's transpose lands the stacked
        # grad accumulator back in host memory. Plain autodiff + per-layer
        # remat — a hand-written custom-VJP walk was tried and REGRESSED:
        # the memory-space pass places dus chains built inside a custom_vjp
        # bwd in HBM (27.8GB at 4B vs ~12.5GB here at 2.5B). Unrolled — a
        # scan would carry the whole stacked array.
        if pp > 1:
            raise ValueError("streamed offload is a single-chip capacity "
                             "feature; it cannot combine with pp")
        devm = _memory_sharding("device")
        shapes = getattr(run, "_slice_shapes", [None] * len(stacked))
        body_c = remat_wrap(body, keep) if use_recompute else body
        out = hidden
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(run.depth):
            slices = []
            for st, ts in zip(stacked, shapes):
                sl = jax.lax.index_in_dim(st, i, keepdims=False)
                if devm is not None:
                    sl = jax.device_put(sl, devm)
                if ts is not None and tuple(sl.shape) != tuple(ts):
                    # host buffer is an aligned [R, 128] slab: restore the
                    # true shape on DEVICE (one unpack definition — the
                    # packer's; lazy import, offload_stream imports us)
                    from ...jit.offload_stream import _unpack_dev

                    sl = _unpack_dev(sl, ts)
                slices.append(sl)
            out, aux_i = body_c(out, tuple(slices))
            aux_total = aux_total + aux_i
        return out, aux_total
    if pp > 1:
        from .pipeline import (choose_microbatches, microbatch,
                               pipeline_shard_map, unmicrobatch)

        if run.depth % pp != 0:
            raise ValueError(
                f"stacked run depth {run.depth} must be divisible by pp={pp}")
        M = choose_microbatches(hidden.shape[0], microbatches or 2 * pp, env)

        def stage_fn(h, *stacked_local):
            out, aux = jax.lax.scan(body, h, tuple(stacked_local))
            return out, jnp.sum(aux)

        x_mb = microbatch(hidden, M, env)
        piped = pipeline_shard_map(stage_fn, env, len(stacked),
                                   remat=use_recompute, with_aux=True,
                                   keep=keep)
        out_mb, aux = piped(x_mb, *stacked)
        return unmicrobatch(out_mb, env), aux / M

    if use_recompute:
        body = remat_wrap(body, keep)
    out, aux = jax.lax.scan(body, hidden, tuple(stacked))
    return out, jnp.sum(aux)


def _run_stack(hidden, *stacked, _run_id, use_recompute, microbatches,
               stream=False, keep=()):
    return _run_stack_fn(hidden, *stacked, _run_id=_run_id,
                         use_recompute=use_recompute, microbatches=microbatches,
                         stream=stream, keep=keep)


def find_homogeneous_run(layers: List[Layer], min_len: int = 2):
    """Longest contiguous [lo, hi) of structurally identical layers — the
    pipelineable middle of a LayerDesc model (reference _segment_network's
    'layer:<Pattern>' balancing picks the same repeated blocks)."""
    best = (0, 0)
    i, n = 0, len(layers)
    while i < n:
        sig = layer_signature(layers[i])
        j = i + 1
        if sig is not None:
            while j < n and layer_signature(layers[j]) == sig:
                j += 1
        if j - i > best[1] - best[0]:
            best = (i, j)
        i = j
    return best if best[1] - best[0] >= min_len else None
