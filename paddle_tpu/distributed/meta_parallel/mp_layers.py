"""Tensor-parallel layers.

Reference: fleet/meta_parallel/parallel_layers/mp_layers.py —
VocabParallelEmbedding(:30), ColumnParallelLinear(:97), RowParallelLinear(:170),
ParallelCrossEntropy(:249), built there on c_identity/c_allreduce/c_concat/
c_embedding collective ops.

TPU-native: the layers hold GSPMD shard specs instead of doing explicit
communication. Weight math is ordinary matmul/gather; placement annotations
(`dist_spec` on parameters + with_sharding_constraint on activations) make XLA
insert the collectives Megatron does — over ICI, fused into the surrounding
compute where profitable. The classes keep the reference's constructor
surface so model code ports unchanged.

How activations lie on an ``mp`` mesh: a layer here constrains only its own
FEATURE dim; the model anchors the leading dims (``models/llama.py:
_mark_seq`` -> ``mesh.activation_spec(shape, "rows")``). Between sublayers
that anchor puts the SEQUENCE over ``mp`` (where ``mp`` divides it), so a
row-parallel layer's partial sums leave as a reduce-scatter over the sequence
and the next column-parallel layer all-gathers its input — Megatron's
sequence-parallel form: the wire carries what the all-reduce carried, but the
gather half is a collective this chip's compiler runs under matmuls, and the
norms and residual adds in between touch 1/mp of the rows. Where ``mp`` does
not divide the sequence the stream stays whole and the sum is an all-reduce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import nn
from ...core.dispatch import primitive
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn import initializer as I
from ..mesh import get_mesh_env


def _mp_degree():
    env = get_mesh_env()
    return env.get_dim("mp") if env is not None else 1


def mark_sharding(x: Tensor, *spec, name=None) -> Tensor:
    """with_sharding_constraint wrapper (annotation no-op off-mesh); ``name``
    is a ``checkpoint_name`` for the constrained value."""
    env = get_mesh_env()
    if env is None:
        return x
    return _shard_constraint(x, spec=tuple(spec), _env_id=id(env), name=name)


# what a row-parallel layer's sum over mp produced — the scattered shard
# where the model anchors the stream sequence-sharded over mp (1/mp of the
# all-reduced value's bytes), the whole value otherwise: the layer scan's
# recompute keeps it under every policy (stage_stack.remat_wrap)
MP_OUT = "mp_out"


def _mark_feature(x: Tensor, feature, name=None) -> Tensor:
    """Constrain only the dim a tensor-parallel layer owns: the last
    (feature) dim is on ``"mp"`` or replicated (``None``). The leading dims
    are left ``UNCONSTRAINED`` — the layer cannot know which of them is the
    batch (dp/sdp) and which the sequence (cp); the model anchors those
    (``models/llama.py:_mark_seq``) and GSPMD propagates them. A ``None``
    there would say "whole on every device" and make every data replica
    gather the global batch and repeat the others' work."""
    return mark_sharding(x, *([P.UNCONSTRAINED] * (x.ndim - 1) + [feature]),
                         name=name)


def constrain_spec(arr, spec):
    """with_sharding_constraint on a raw array, robust to being inside a
    partial-manual shard_map (the pp pipeline): constraints there must be
    built on the context AbstractMesh with its Manual axes stripped (pp
    handoff is explicit)."""
    env = get_mesh_env()
    if env is None:
        return arr
    try:
        am = jax.sharding.get_abstract_mesh()
    except AttributeError:  # jax < 0.7: no AbstractMesh context accessor
        am = None
    if am is not None and not am.empty and am._any_axis_manual:
        manual = {name for name, ty in zip(am.axis_names, am.axis_types)
                  if "Manual" in str(ty)}
        mesh_for_ns = am
    else:
        # older jax: inside a shard_map trace the mesh axes are bound in the
        # axis env; stripping ALL of them from the spec is safe (a weaker
        # constraint, never a wrong one) and required for the manual ones
        try:
            from jax._src import core as _core_src

            manual = {n for n in _core_src.get_axis_env().axis_sizes
                      if isinstance(n, str)}
        except Exception:
            manual = set()
        mesh_for_ns = env.mesh

    if manual:
        def strip(entry):
            if entry is None or entry is P.UNCONSTRAINED:
                return entry
            if isinstance(entry, (tuple, list)):
                kept = tuple(e for e in entry if e not in manual)
                return kept or None
            return None if entry in manual else entry

        ns = NamedSharding(mesh_for_ns, P(*(strip(e) for e in spec)))
    else:
        ns = NamedSharding(env.mesh, P(*spec))
    return jax.lax.with_sharding_constraint(arr, ns)


@primitive("shard_constraint")
def _shard_constraint(x, *, spec, _env_id, name=None):
    out = constrain_spec(x, spec)
    if name is not None:
        from jax.ad_checkpoint import checkpoint_name

        out = checkpoint_name(out, name)
    return out


class VocabParallelEmbedding(nn.Layer):
    """Embedding with the vocab dim sharded over mp (reference mp_layers.py:30)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None, mp_group=None,
                 name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight.dist_spec = P("mp", None)
        self.weight.is_distributed = True

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return _mark_feature(out, None) if out.ndim == 3 else out


class ColumnParallelLinear(nn.Layer):
    """Weight [in, out] sharded on out (columns) over mp (mp_layers.py:97)."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 gather_output=True, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierUniform())
        self.weight.dist_spec = P(None, "mp")
        self.weight.is_distributed = True
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True)
            self.bias.dist_spec = P("mp")
            self.bias.is_distributed = True
        else:
            self.bias = None
            self._parameters["bias"] = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        # gather_output: the feature dim whole on every mp rank (XLA inserts
        # the all-gather); else it stays sharded over mp
        return _mark_feature(out, None if self.gather_output else "mp")


class RowParallelLinear(nn.Layer):
    """Weight [in, out] sharded on in (rows) over mp (mp_layers.py:170)."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 input_is_parallel=False, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierUniform())
        self.weight.dist_spec = P("mp", None)
        self.weight.is_distributed = True
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True)
        else:
            self.bias = None
            self._parameters["bias"] = None

    def forward(self, x):
        if self.input_is_parallel:
            x = _mark_feature(x, "mp")
        out = F.linear(x, self.weight, None)
        # partial sums reduce here: the feature dim whole, the leading dims
        # left to the model's anchor behind — sequence over mp there, so XLA
        # inserts a reduce-scatter (an all-reduce where the stream stays
        # whole). Where mp really splits the rows the sum crossed the wire:
        # it is named, so a recompute keeps it instead of reducing again
        out = _mark_feature(out, None,
                            name=MP_OUT if _mp_degree() > 1 else None)
        if self.bias is not None:
            out = out + self.bias
        return out


class ParallelCrossEntropy(nn.Layer):
    """CE over mp-sharded logits (mp_layers.py:249,
    c_softmax_with_cross_entropy role). GSPMD computes the sharded
    softmax+gather with the needed all-reduces from the annotation."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        logits = _mark_feature(input, "mp")
        return F.cross_entropy(logits, label, reduction="none",
                               ignore_index=self.ignore_index)
