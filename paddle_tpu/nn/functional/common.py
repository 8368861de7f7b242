"""Common nn functionals: linear, embedding, dropout, normalization, pooling,
interpolate (reference: python/paddle/nn/functional/{common,norm,pooling}.py).

Convs/pools use lax.conv_general_dilated / lax.reduce_window directly — the MXU
path for convs, fused window reductions for pools.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import primitive
from ...core.tensor import Tensor
from ...framework import random as random_mod
from ...framework import dtype as dtype_mod


@primitive("linear_op")
def _linear(x, w, b):
    return jnp.matmul(x, w) + b


@primitive("linear_nobias_op")
def _linear_nb(x, w):
    return jnp.matmul(x, w)


def linear(x, weight, bias=None, name=None):
    if bias is None:
        return _linear_nb(x, weight)
    return _linear(x, weight, bias)


@primitive("embedding_op")
def _embedding(w, ids, *, padding_idx, oov=None):
    if oov == "clip":
        ids = jnp.clip(ids, 0, w.shape[0] - 1)
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None:
        mask = (ids == padding_idx)[..., None]
        out = jnp.where(mask, 0.0, out)
    return out


@_embedding.defvjp
def _embedding_vjp(ct, out, primals, *, padding_idx, oov=None):
    w, ids = primals
    if oov == "clip":
        ids = jnp.clip(ids, 0, w.shape[0] - 1)
    if padding_idx is not None:
        ct = jnp.where((ids == padding_idx)[..., None], 0.0, ct)
    gw = jnp.zeros_like(w).at[ids].add(ct.astype(w.dtype))
    return (gw, None)


def embedding(x, weight, padding_idx=None, sparse=False, name=None,
              oov_policy=None):
    """Row lookup with an EXPLICIT out-of-vocabulary policy.

    ``jnp.take`` clamps out-of-range ids silently — a recsys id stream
    with a hashing bug would train on row 0/row n-1 garbage without a
    peep. Policy (``FLAGS_embedding_oov_policy`` default, per-call
    override): ``'error'`` raises on concrete eager ids outside
    ``[0, num_rows)`` (inside a traced program ids are abstract — the
    check cannot run and the clamped gather remains, documented);
    ``'clip'`` opts into the clamp everywhere and makes it part of the
    op's cache key (the attr rides the jit key, so flipping policies
    retraces auditable)."""
    from ...framework import flags as _flags

    policy = oov_policy or _flags.flag("embedding_oov_policy")
    if policy not in ("error", "clip"):
        raise ValueError(
            f"embedding oov_policy must be 'error' or 'clip', got "
            f"{policy!r}")
    if policy == "error":
        ids = x.data if isinstance(x, Tensor) else x
        if not isinstance(ids, jax.core.Tracer):
            if not isinstance(ids, jax.Array):
                ids = np.asarray(ids)  # lists/scalars are checkable too
        if not isinstance(ids, jax.core.Tracer) and \
                getattr(ids, "size", 0):
            n = int((weight.data if isinstance(weight, Tensor)
                     else weight).shape[0])
            if isinstance(ids, np.ndarray):
                # host ids validate host-side (no H2D round-trip)
                lo, hi = int(ids.min()), int(ids.max())
            else:
                bounds = jnp.stack([jnp.min(ids), jnp.max(ids)])
                if isinstance(bounds, jax.core.Tracer):
                    # CONCRETE device ids under an AMBIENT trace (an
                    # upstream op ran through an AOT-compiled executable
                    # — persistent-cache per-op jits): the reduction was
                    # STAGED, so there is nothing to read back. Same
                    # contract as tracer ids: traced programs are
                    # documented unchecked.
                    lo, hi = 0, -1
                else:
                    # ONE blocking readback for both bounds, not two
                    lo, hi = (int(v) for v in np.asarray(bounds))
            if lo < 0 or hi >= n:
                raise ValueError(
                    f"embedding: id out of range [0, {n}) "
                    f"(min={lo}, max={hi}); pass oov_policy='clip' or set "
                    f"FLAGS_embedding_oov_policy='clip' for the clamped "
                    f"legacy behavior")
    return _embedding(weight, x, padding_idx=padding_idx,
                      oov=("clip" if policy == "clip" else None))


@primitive("dropout_op")
def _dropout(x, key, *, p, upscale):
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    if upscale:
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    return jnp.where(keep, x, 0.0).astype(x.dtype)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            from ...ops import math as _math

            return _math.scale(x, 1.0 - p)
        return x
    return _dropout(x, random_mod.next_key(), p=float(p), upscale=(mode == "upscale_in_train"))


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    if not training or p == 0.0:
        return x
    return _dropout2d(x, random_mod.next_key(), p=float(p), nchw=(data_format == "NCHW"))


@primitive("dropout2d_op")
def _dropout2d(x, key, *, p, nchw):
    shape = (x.shape[0], x.shape[1], 1, 1) if nchw else (x.shape[0], 1, 1, x.shape[3])
    keep = jax.random.bernoulli(key, 1.0 - p, shape)
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


# -- normalization -----------------------------------------------------------

@primitive("layer_norm_op")
def _layer_norm(x, w, b, *, eps, begin_axis):
    axes = tuple(range(begin_axis, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    xn = (x - mean) * jax.lax.rsqrt(var + eps)
    return xn * w + b


@primitive("layer_norm_nowb_op")
def _layer_norm_nowb(x, *, eps, begin_axis):
    axes = tuple(range(begin_axis, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    begin_axis = x.ndim - len(normalized_shape)
    if weight is None:
        return _layer_norm_nowb(x, eps=float(epsilon), begin_axis=begin_axis)
    return _layer_norm(x, weight, bias, eps=float(epsilon), begin_axis=begin_axis)


@primitive("rms_norm_op")
def _rms_norm(x, w, *, eps, impl):
    from ...kernels.pallas import rmsnorm

    if impl == "reference":  # plain jnp: GSPMD partitions it itself
        return rmsnorm.rms_norm(x, w, eps, impl)
    from jax.sharding import PartitionSpec as P

    from ...distributed.mesh import activation_spec, run_kernel_on_mesh

    # the rows split over the data axes (and cp), w whole on every device;
    # residuals (x, w, rstd): rstd [..., 1] has the rows' layout
    spec = activation_spec(x.shape, "rows")
    return run_kernel_on_mesh(
        *rmsnorm.rms_norm_halves(eps, impl), (x, w), in_specs=(spec, P()),
        out_specs=spec, res_specs=(spec, P(), spec))


@primitive("rms_norm_residual_op")
def _rms_norm_residual(x, res, w, *, eps, impl):
    """Pre-norm decoder pattern ``s = x + res; y = rmsnorm(s)`` ->
    (y, s), one HBM pass where the Pallas kernel runs."""
    from ...kernels.pallas import rmsnorm

    if impl == "reference":
        return rmsnorm.rms_norm_residual(x, res, w, eps, impl)
    from jax.sharding import PartitionSpec as P

    from ...distributed.mesh import activation_spec, run_kernel_on_mesh

    spec = activation_spec(x.shape, "rows")  # residuals (s, w, rstd)
    return run_kernel_on_mesh(
        *rmsnorm.rms_norm_residual_halves(eps, impl), (x, res, w),
        in_specs=(spec, spec, P()), out_specs=(spec, spec),
        res_specs=(spec, P(), spec))


def rms_norm(x, weight, epsilon=1e-6, name=None):
    """RMSNorm (not in the reference snapshot; required by the Llama
    family). The implementation ``kernels.registry.resolve`` picks rides
    the jit cache key as an attr, so a change of it (another live mesh)
    retraces (retrace-auditable)."""
    from ...kernels.registry import resolve

    return _rms_norm(x, weight, eps=float(epsilon), impl=resolve("rms_norm"))


def rms_norm_residual(x, residual, weight, epsilon=1e-6, name=None):
    """Fused residual-add + RMSNorm -> ``(normed, new_residual)`` — the
    decoder-layer hot pattern (see docs/performance.md "Fused kernels")."""
    from ...kernels.registry import resolve

    return _rms_norm_residual(x, residual, weight, eps=float(epsilon),
                              impl=resolve("rms_norm"))


@primitive("batch_norm_infer_op")
def _bn_infer(x, mean, var, w, b, *, eps, axis):
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    mean = mean.reshape(shape)
    var = var.reshape(shape)
    w = w.reshape(shape)
    b = b.reshape(shape)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


@primitive("batch_norm_train_op")
def _bn_train(x, w, b, *, eps, axis):
    axes = tuple(i for i in range(x.ndim) if i != axis)
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    xn = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + eps)
    return xn * w.reshape(shape) + b.reshape(shape), mean, var


def batch_norm(x, running_mean, running_var, weight, bias, training=False,
               momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None, name=None):
    axis = 1 if data_format.startswith("NC") else x.ndim - 1
    if use_global_stats is None:
        use_global_stats = not training
    if use_global_stats:
        return _bn_infer(x, running_mean, running_var, weight, bias, eps=float(epsilon), axis=axis)
    out, batch_mean, batch_var = _bn_train(x, weight, bias, eps=float(epsilon), axis=axis)
    # update running stats in place (matches reference's batch_norm mean/var outputs)
    if isinstance(running_mean, Tensor):
        m = momentum
        running_mean.set_value(m * running_mean.data + (1 - m) * batch_mean.data)
        # reference accumulates the *biased* saved variance
        # (paddle/phi/kernels/cpu/batch_norm_kernel.cc running_var update)
        running_var.set_value(m * running_var.data + (1 - m) * batch_var.data)
    return out


@primitive("group_norm_op")
def _group_norm(x, w, b, *, groups, eps):
    n, c = x.shape[0], x.shape[1]
    gshape = (n, groups, c // groups) + x.shape[2:]
    xg = x.reshape(gshape)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    xn = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    shape = [1, c] + [1] * (x.ndim - 2)
    return xn * w.reshape(shape) + b.reshape(shape)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5, data_format="NCHW", name=None):
    from ...ops import creation

    if weight is None:
        weight = creation.ones([x.shape[1]], x.dtype)
    if bias is None:
        bias = creation.zeros([x.shape[1]], x.dtype)
    return _group_norm(x, weight, bias, groups=int(num_groups), eps=float(epsilon))


@primitive("instance_norm_op")
def _instance_norm(x, w, b, *, eps):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    xn = (x - mean) * jax.lax.rsqrt(var + eps)
    shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
    return xn * w.reshape(shape) + b.reshape(shape)


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    from ...ops import creation

    if weight is None:
        weight = creation.ones([x.shape[1]], x.dtype)
    if bias is None:
        bias = creation.zeros([x.shape[1]], x.dtype)
    return _instance_norm(x, weight, bias, eps=float(eps))


@primitive("l2_normalize_op")
def _normalize(x, *, p, axis, eps):
    norm = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True), 1.0 / p)
    return x / jnp.maximum(norm, eps)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return _normalize(x, p=float(p), axis=int(axis), eps=float(epsilon))


# -- convolution / pooling ---------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


@primitive("conv2d_op")
def _conv2d(x, w, *, stride, padding, dilation, groups, nchw):
    dn = ("NCHW", "OIHW", "NCHW") if nchw else ("NHWC", "HWIO", "NHWC")
    if isinstance(padding, str):
        pad = padding
    else:
        pad = [(p, p) for p in padding] if len(padding) == 2 else [
            tuple(padding[0:2]), tuple(padding[2:4])]
    return jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=pad, rhs_dilation=dilation,
        feature_group_count=groups, dimension_numbers=dn,
    )


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    nchw = data_format == "NCHW"
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        pad = _pair(padding) if not isinstance(padding, (list, tuple)) or len(padding) <= 4 else padding
    out = _conv2d(
        x, weight, stride=_pair(stride), padding=pad if isinstance(pad, str) else tuple(pad),
        dilation=_pair(dilation), groups=int(groups), nchw=nchw,
    )
    if bias is not None:
        from ...ops import manipulation

        shape = [1, -1, 1, 1] if nchw else [1, 1, 1, -1]
        out = out + manipulation.reshape(bias, shape)
    return out


@primitive("conv1d_op")
def _conv1d(x, w, *, stride, padding, dilation, groups):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding=[(padding, padding)],
        rhs_dilation=(dilation,), feature_group_count=groups,
        dimension_numbers=("NCH", "OIH", "NCH"),
    )


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    out = _conv1d(x, weight, stride=int(stride), padding=int(padding),
                  dilation=int(dilation), groups=int(groups))
    if bias is not None:
        from ...ops import manipulation

        out = out + manipulation.reshape(bias, [1, -1, 1])
    return out


@primitive("conv2d_transpose_op")
def _conv2d_transpose(x, w, *, stride, padding, dilation, out_pad, groups):
    # paddle stores the transpose kernel as [in, out//groups, kh, kw]
    # (python/paddle/nn/layer/conv.py Conv2DTranspose). Express the op as the
    # gradient of a forward conv: flip spatial dims, swap I/O per group, then a
    # fractionally-strided (lhs_dilated) conv with gradient padding
    # lo = hi = dilation*(k-1) - p, plus output_padding on the high side —
    # matching paddle's out = (H-1)*s - 2p + d*(k-1) + 1 + op.
    g = groups
    cin, cog, kh, kw = w.shape
    w = jnp.flip(w, axis=(2, 3))
    w = w.reshape(g, cin // g, cog, kh, kw)
    w = jnp.transpose(w, (0, 2, 1, 3, 4)).reshape(g * cog, cin // g, kh, kw)
    pads = [
        (dilation[i] * (k - 1) - padding[i],
         dilation[i] * (k - 1) - padding[i] + out_pad[i])
        for i, k in enumerate((kh, kw))
    ]
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pads, lhs_dilation=stride,
        rhs_dilation=dilation, feature_group_count=g,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, data_format="NCHW", output_size=None, name=None):
    if data_format == "NHWC":  # compute in NCHW, transpose at the edges
        from ...ops import manipulation as _m

        out = conv2d_transpose(_m.transpose(x, [0, 3, 1, 2]), weight, bias,
                               stride, padding, output_padding, groups,
                               dilation, "NCHW", output_size)
        return _m.transpose(out, [0, 2, 3, 1])
    if data_format != "NCHW":
        raise ValueError(f"conv2d_transpose: bad data_format {data_format!r}")
    st, pd, dl = _pair(stride), _pair(padding), _pair(dilation)
    op = _pair(output_padding)
    if output_size is not None:
        if op != (0, 0):
            raise ValueError(
                "output_padding and output_size can not be both set")
        if isinstance(output_size, Tensor):
            output_size = output_size.tolist()
        osz = _pair(output_size)
        kh, kw = weight.shape[2], weight.shape[3]
        op = tuple(
            osz[i] - ((x.shape[2 + i] - 1) * st[i] - 2 * pd[i] + dl[i] * (k - 1) + 1)
            for i, k in enumerate((kh, kw))
        )
        for i in range(2):
            if not 0 <= op[i] < st[i]:
                raise ValueError(
                    f"output_size[{i}]={osz[i]} is out of the legal range "
                    f"[min, min+stride) for the given input/kernel/stride")
    out = _conv2d_transpose(x, weight, stride=st, padding=pd, dilation=dl,
                            out_pad=op, groups=int(groups))
    if bias is not None:
        from ...ops import manipulation

        out = out + manipulation.reshape(bias, [1, -1, 1, 1])
    return out


@primitive("max_pool2d_op")
def _max_pool2d(x, *, ksize, stride, padding, nchw):
    window = (1, 1) + ksize if nchw else (1,) + ksize + (1,)
    strides = (1, 1) + stride if nchw else (1,) + stride + (1,)
    pads = ((0, 0), (0, 0)) + tuple((p, p) for p in padding) if nchw else \
        ((0, 0),) + tuple((p, p) for p in padding) + ((0, 0),)
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides, pads)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    ks = _pair(kernel_size)
    st = _pair(stride) if stride is not None else ks
    out = _max_pool2d(x, ksize=ks, stride=st, padding=_pair(padding),
                      nchw=data_format == "NCHW")
    if return_mask:
        if data_format != "NCHW":
            raise ValueError("max_pool2d return_mask requires NCHW")
        return out, _max_pool_nd_mask(x, ksize=ks, stride=st,
                                      padding=_pair(padding))
    return out


@primitive("avg_pool2d_op")
def _avg_pool2d(x, *, ksize, stride, padding, nchw, count_include_pad):
    window = (1, 1) + ksize if nchw else (1,) + ksize + (1,)
    strides = (1, 1) + stride if nchw else (1,) + stride + (1,)
    pads = ((0, 0), (0, 0)) + tuple((p, p) for p in padding) if nchw else \
        ((0, 0),) + tuple((p, p) for p in padding) + ((0, 0),)
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if count_include_pad or all(p == 0 for p in padding):
        denom = np.prod(ksize)
        return summed / denom
    ones = jnp.ones_like(x)
    counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
    return summed / counts


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    ks = _pair(kernel_size)
    st = _pair(stride) if stride is not None else ks
    return _avg_pool2d(
        x, ksize=ks, stride=st, padding=_pair(padding), nchw=data_format == "NCHW",
        count_include_pad=not exclusive,
    )


def _adaptive_bins(size, out):
    """torch/paddle adaptive pooling bin edges: start=floor(i*s/o),
    end=ceil((i+1)*s/o). Static python ints — fine under jit."""
    return [(i * size // out, -(-(i + 1) * size // out)) for i in range(out)]


def _adaptive_pool2d_body(x, out_hw, reduce_fn):
    """Shared divisible-fast-path + general bin loop (NCHW)."""
    n, c, h, w = x.shape
    oh, ow = out_hw
    if h % oh == 0 and w % ow == 0:  # fast path: one reshape-reduce
        return reduce_fn(x.reshape(n, c, oh, h // oh, ow, w // ow), (3, 5))
    rows = []
    for hs, he in _adaptive_bins(h, oh):
        cols = [reduce_fn(x[:, :, hs:he, ws:we], (2, 3))
                for ws, we in _adaptive_bins(w, ow)]
        rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(rows, axis=-2)


@primitive("adaptive_avg_pool2d_op")
def _adaptive_avg_pool2d(x, *, out_hw):
    return _adaptive_pool2d_body(x, out_hw, lambda v, ax: jnp.mean(v, axis=ax))


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_avg_pool2d(x, out_hw=_pair(output_size))


@primitive("adaptive_max_pool2d_op")
def _adaptive_max_pool2d_any(x, *, out_hw):
    return _adaptive_pool2d_body(x, out_hw, lambda v, ax: jnp.max(v, axis=ax))


@primitive("adaptive_max_pool2d_mask_op", nondiff=True)
def _adaptive_max_pool2d_mask(x, *, out_hw):
    """Flattened H*W argmax index per output cell (the reference's mask)."""
    n, c, h, w = x.shape
    oh, ow = out_hw
    rows = []
    for hs, he in _adaptive_bins(h, oh):
        cols = []
        for ws, we in _adaptive_bins(w, ow):
            win = x[:, :, hs:he, ws:we].reshape(n, c, -1)
            flat = jnp.argmax(win, axis=-1)
            wh = we - ws
            gh = hs + flat // wh
            gw = ws + flat % wh
            cols.append(gh * w + gw)
        rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(rows, axis=-2).astype(jnp.int32)


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    out = _adaptive_max_pool2d_any(x, out_hw=_pair(output_size))
    if return_mask:
        return out, _adaptive_max_pool2d_mask(x, out_hw=_pair(output_size))
    return out


@primitive("interpolate_nearest_op")
def _interp_nearest(x, *, size):
    return jax.image.resize(x, x.shape[:2] + size, method="nearest")


@primitive("interpolate_bilinear_op")
def _interp_bilinear(x, *, size)  :
    return jax.image.resize(x, x.shape[:2] + size, method="bilinear")


@primitive("interpolate_bicubic_op")
def _interp_bicubic(x, *, size):
    return jax.image.resize(x, x.shape[:2] + size, method="cubic")


@primitive("interpolate_trilinear_op")
def _interp_trilinear(x, *, size):
    return jax.image.resize(x, x.shape[:2] + size, method="linear")


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW", name=None):
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else [scale_factor] * 2
        size = (int(x.shape[2] * sf[0]), int(x.shape[3] * sf[1]))
    else:
        if isinstance(size, Tensor):
            size = size.tolist()
        size = tuple(int(s) for s in size)
    if mode == "nearest":
        return _interp_nearest(x, size=tuple(size))
    if mode in ("bilinear", "linear"):
        return _interp_bilinear(x, size=tuple(size))
    if mode in ("bicubic", "cubic"):
        return _interp_bicubic(x, size=tuple(size))
    if mode == "area":
        # paddle's area mode IS adaptive average pooling over the target grid
        return _adaptive_avg_pool2d(x, out_hw=tuple(size))
    if mode == "trilinear" and x.ndim == 5:
        return _interp_trilinear(x, size=tuple(size))
    raise ValueError(f"interpolate: unsupported mode {mode!r}")


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode, data_format)


@primitive("pixel_shuffle_op")
def _pixel_shuffle(x, *, factor):
    n, c, h, w = x.shape
    r = factor
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(n, c // (r * r), h * r, w * r)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    return _pixel_shuffle(x, factor=int(upscale_factor))


@primitive("unfold_op")
def _unfold(x, *, ksize, stride, padding, dilation):
    n, c, h, w = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=ksize, window_strides=stride,
        padding=[(padding[0], padding[0]), (padding[1], padding[1])],
        rhs_dilation=dilation, dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return patches.reshape(n, patches.shape[1], -1)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    return _unfold(x, ksize=_pair(kernel_sizes), stride=_pair(strides),
                   padding=_pair(paddings), dilation=_pair(dilations))


@primitive("cosine_similarity_op")
def _cosine_similarity(x1, x2, *, axis, eps):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.sqrt(jnp.sum(jnp.square(x1), axis=axis))
    n2 = jnp.sqrt(jnp.sum(jnp.square(x2), axis=axis))
    return dot / jnp.maximum(n1 * n2, eps)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    return _cosine_similarity(x1, x2, axis=int(axis), eps=float(eps))


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    from ...ops import manipulation

    return manipulation.pad(x, pad, mode, value, data_format)


# -- 1-D / 3-D pooling + conv family (round-3 API completion) ----------------
# One generic N-spatial-dim reduce_window body serves every rank; the 2-D
# code above predates it and stays as-is (hot path, already tuned).

def _tuple_n(v, n):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


@primitive("pool_nd_op")
def _pool_nd(x, *, ksize, stride, padding, kind, count_include_pad):
    nd = len(ksize)
    window = (1, 1) + ksize
    strides = (1, 1) + stride
    pads = ((0, 0), (0, 0)) + tuple((p, p) for p in padding)
    if kind == "max":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window,
                                     strides, pads)
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if count_include_pad or all(p == 0 for p in padding):
        return summed / np.prod(ksize)
    counts = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                   window, strides, pads)
    return summed / counts


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, name=None):
    ks = _tuple_n(kernel_size, 1)
    st = _tuple_n(stride, 1) if stride is not None else ks
    out = _pool_nd(x, ksize=ks, stride=st, padding=_tuple_n(padding, 1),
                   kind="max", count_include_pad=True)
    if return_mask:
        return out, _max_pool_nd_mask(x, ksize=ks, stride=st,
                                      padding=_tuple_n(padding, 1))
    return out


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    ks = _tuple_n(kernel_size, 1)
    st = _tuple_n(stride, 1) if stride is not None else ks
    return _pool_nd(x, ksize=ks, stride=st, padding=_tuple_n(padding, 1),
                    kind="avg", count_include_pad=not exclusive)


def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCDHW", name=None):
    ks = _tuple_n(kernel_size, 3)
    st = _tuple_n(stride, 3) if stride is not None else ks
    out = _pool_nd(x, ksize=ks, stride=st, padding=_tuple_n(padding, 3),
                   kind="max", count_include_pad=True)
    if return_mask:
        return out, _max_pool_nd_mask(x, ksize=ks, stride=st,
                                      padding=_tuple_n(padding, 3))
    return out


def avg_pool3d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, divisor_override=None, data_format="NCDHW",
               name=None):
    ks = _tuple_n(kernel_size, 3)
    st = _tuple_n(stride, 3) if stride is not None else ks
    return _pool_nd(x, ksize=ks, stride=st, padding=_tuple_n(padding, 3),
                    kind="avg", count_include_pad=not exclusive)


@primitive("max_pool_nd_mask_op", nondiff=True)
def _max_pool_nd_mask(x, *, ksize, stride, padding):
    """Flattened spatial argmax index per window (paddle's unpool mask)."""
    nd = len(ksize)
    spatial = x.shape[2:]
    flat_sizes = np.array(spatial)
    # linear index of every input position
    lin = jnp.arange(int(np.prod(spatial))).reshape(spatial)
    lin = jnp.broadcast_to(lin, x.shape)
    if any(padding):
        padcfg = [(0, 0), (0, 0)] + [(p, p) for p in padding]
        xp = jnp.pad(x, padcfg, constant_values=-jnp.inf)
        linp = jnp.pad(lin, padcfg, constant_values=-1)
    else:
        xp, linp = x, lin
    window = (1, 1) + tuple(ksize)
    strides = (1, 1) + tuple(stride)
    # argmax via reduce_window over (value, index) pairs
    def sel(a, b):
        av, ai = a
        bv, bi = b
        take_b = bv > av
        return jnp.where(take_b, bv, av), jnp.where(take_b, bi, ai)
    vals, idxs = jax.lax.reduce_window(
        (xp, linp.astype(jnp.int32)), (-jnp.inf, jnp.int32(-1)), sel,
        window, strides, [(0, 0)] * (nd + 2))
    return idxs


@primitive("max_unpool_nd_op")
def _max_unpool_nd(x, indices, *, out_spatial):
    n, c = x.shape[:2]
    flat = int(np.prod(out_spatial))
    xf = x.reshape(n, c, -1)
    idx = indices.reshape(n, c, -1).astype(jnp.int32)
    out = jnp.zeros((n, c, flat), x.dtype)
    bi = jnp.arange(n)[:, None, None]
    ci = jnp.arange(c)[None, :, None]
    out = out.at[bi, ci, idx].set(xf)
    return out.reshape((n, c) + out_spatial)


def _unpool(x, indices, kernel_size, stride, padding, output_size, nd):
    ks = _tuple_n(kernel_size, nd)
    st = _tuple_n(stride, nd) if stride is not None else ks
    if output_size is None:
        out_spatial = tuple(
            (s - 1) * st[i] + ks[i] - 2 * _tuple_n(padding, nd)[i]
            for i, s in enumerate(x.shape[2:]))
    else:
        out_spatial = tuple(int(d) for d in output_size[-nd:])
    return _max_unpool_nd(x, indices, out_spatial=out_spatial)


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None, name=None):
    return _unpool(x, indices, kernel_size, stride, padding, output_size, 1)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    return _unpool(x, indices, kernel_size, stride, padding, output_size, 2)


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None, name=None):
    return _unpool(x, indices, kernel_size, stride, padding, output_size, 3)


def _adaptive_pool_nd(x, out_sizes, reduce_fn):
    spatial = x.shape[2:]
    if all(s % o == 0 for s, o in zip(spatial, out_sizes)):
        shape = list(x.shape[:2])
        axes = []
        for i, (s, o) in enumerate(zip(spatial, out_sizes)):
            shape += [o, s // o]
            axes.append(2 + 2 * i + 1)
        return reduce_fn(x.reshape(shape), tuple(axes))
    # general bins: recursive per-dim construction (rare path, small outputs)
    def build(prefix_idx, t):
        dim = len(prefix_idx)
        if dim == len(out_sizes):
            return reduce_fn(t, tuple(range(2, 2 + len(out_sizes))))
        res = []
        for a, b in _adaptive_bins(t.shape[2 + dim], out_sizes[dim]):
            idx = [slice(None)] * t.ndim
            idx[2 + dim] = slice(a, b)
            res.append(build(prefix_idx + (0,), t[tuple(idx)]))
        return jnp.stack(res, axis=2 + dim)
    return build((), x)


@primitive("adaptive_pool_nd_op")
def _adaptive_pool_nd_prim(x, *, out_sizes, kind):
    fn = {"avg": lambda v, ax: jnp.mean(v, axis=ax),
          "max": lambda v, ax: jnp.max(v, axis=ax)}[kind]
    return _adaptive_pool_nd(x, out_sizes, fn)


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive_pool_nd_prim(x, out_sizes=_tuple_n(output_size, 1),
                                  kind="avg")


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    out = _adaptive_pool_nd_prim(x, out_sizes=_tuple_n(output_size, 1),
                                 kind="max")
    if return_mask:
        raise ValueError("adaptive_max_pool1d return_mask: use "
                         "adaptive_max_pool2d on an unsqueezed input")
    return out


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive_pool_nd_prim(x, out_sizes=_tuple_n(output_size, 3),
                                  kind="avg")


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    out = _adaptive_pool_nd_prim(x, out_sizes=_tuple_n(output_size, 3),
                                 kind="max")
    if return_mask:
        raise ValueError("adaptive_max_pool3d return_mask is not provided; "
                         "derive indices via max_pool3d(return_mask=True)")
    return out


@primitive("conv3d_op")
def _conv3d(x, w, *, stride, padding, dilation, groups):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=[(p, p) for p in padding],
        rhs_dilation=dilation, feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    out = _conv3d(x, weight, stride=_tuple_n(stride, 3),
                  padding=_tuple_n(padding, 3),
                  dilation=_tuple_n(dilation, 3), groups=int(groups))
    if bias is not None:
        from ...ops import manipulation

        out = out + manipulation.reshape(bias, [1, -1, 1, 1, 1])
    return out


@primitive("conv_transpose_nd_op")
def _conv_transpose_nd(x, w, *, stride, padding, dilation, out_pad, groups):
    nd = len(stride)
    g = groups
    cin = w.shape[0]
    cog = w.shape[1]
    k = w.shape[2:]
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    w = w.reshape((g, cin // g, cog) + k)
    w = jnp.moveaxis(w, 2, 1).reshape((g * cog, cin // g) + k)
    pads = [
        (dilation[i] * (k[i] - 1) - padding[i],
         dilation[i] * (k[i] - 1) - padding[i] + out_pad[i])
        for i in range(nd)
    ]
    spec = "NC" + "DHW"[-nd:]
    wspec = "OI" + "DHW"[-nd:]
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1,) * nd, padding=pads, lhs_dilation=stride,
        rhs_dilation=dilation, feature_group_count=g,
        dimension_numbers=(spec, wspec, spec))


def _out_pad_from_size(x, weight, output_size, st, pd, dl, nd):
    """Same conversion conv2d_transpose does: requested output size ->
    output_padding, validated against the [min, min+stride) legal range."""
    if isinstance(output_size, Tensor):
        output_size = output_size.tolist()
    osz = _tuple_n(output_size, nd)
    ks = weight.shape[2:]
    op = tuple(
        osz[i] - ((x.shape[2 + i] - 1) * st[i] - 2 * pd[i]
                  + dl[i] * (ks[i] - 1) + 1)
        for i in range(nd))
    for i in range(nd):
        if not 0 <= op[i] < st[i]:
            raise ValueError(
                f"output_size[{i}]={osz[i]} is out of the legal range "
                "[min, min+stride) for the given input/kernel/stride")
    return op


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    st, pd, dl = _tuple_n(stride, 1), _tuple_n(padding, 1), _tuple_n(dilation, 1)
    op = _tuple_n(output_padding, 1)
    if output_size is not None:
        if op != (0,):
            raise ValueError("output_padding and output_size can not be both set")
        op = _out_pad_from_size(x, weight, output_size, st, pd, dl, 1)
    out = _conv_transpose_nd(
        x, weight, stride=st, padding=pd, dilation=dl, out_pad=op,
        groups=int(groups))
    if bias is not None:
        from ...ops import manipulation

        out = out + manipulation.reshape(bias, [1, -1, 1])
    return out


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    st, pd, dl = _tuple_n(stride, 3), _tuple_n(padding, 3), _tuple_n(dilation, 3)
    op = _tuple_n(output_padding, 3)
    if output_size is not None:
        if op != (0, 0, 0):
            raise ValueError("output_padding and output_size can not be both set")
        op = _out_pad_from_size(x, weight, output_size, st, pd, dl, 3)
    out = _conv_transpose_nd(
        x, weight, stride=st, padding=pd, dilation=dl, out_pad=op,
        groups=int(groups))
    if bias is not None:
        from ...ops import manipulation

        out = out + manipulation.reshape(bias, [1, -1, 1, 1, 1])
    return out


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    """Whole-channel dropout for 5-D inputs (reference dropout3d)."""
    if not training or p == 0.0:
        return x
    from ...framework import random as random_mod
    from ...ops import creation

    keep = creation.rand([x.shape[0], x.shape[1], 1, 1, 1]) >= p
    from ...ops import manipulation as _m

    mask = _m.cast(keep, str(x.dtype)) / (1.0 - p)
    return x * mask


def alpha_dropout(x, p=0.5, training=True, name=None):
    """SELU-preserving dropout (reference alpha_dropout): keeps mean/var of
    self-normalizing activations."""
    if not training or p == 0.0:
        return x
    from ...ops import creation, manipulation as _m
    import math as _math

    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = creation.rand(list(x.shape)) >= p
    mask = _m.cast(keep, str(x.dtype))
    a = (1.0 / _math.sqrt((1 - p) * (1 + p * alpha_p ** 2))) \
        if (1 - p) * (1 + p * alpha_p ** 2) > 0 else 1.0
    b = -a * alpha_p * p
    return a * (x * mask + alpha_p * (1.0 - mask)) + b


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    """AlexNet LRN across channels (reference local_response_norm)."""
    sq = x * x
    from ...ops import manipulation as _m

    pad_lo = (size - 1) // 2
    pad_hi = size - 1 - pad_lo
    sq_sum = _lrn_sum(sq, pad_lo=pad_lo, pad_hi=pad_hi, size=size)
    return x / (k + alpha * sq_sum) ** beta


@primitive("lrn_sum_op")
def _lrn_sum(sq, *, pad_lo, pad_hi, size):
    padded = jnp.pad(sq, [(0, 0), (pad_lo, pad_hi)] +
                     [(0, 0)] * (sq.ndim - 2))
    return jax.lax.reduce_window(
        padded, 0.0, jax.lax.add, (1, size) + (1,) * (sq.ndim - 2),
        (1,) * sq.ndim, [(0, 0)] * sq.ndim)


@primitive("bilinear_op")
def _bilinear(x1, x2, w, b):
    # w: [out, in1, in2] -> out[n,o] = x1[n,i] w[o,i,j] x2[n,j] + b
    out = jnp.einsum("ni,oij,nj->no", x1, w, x2)
    return out + b if b is not None else out


def bilinear(x1, x2, weight, bias=None, name=None):
    if bias is None:
        from ...ops import creation

        bias = creation.zeros([1, weight.shape[0]], str(weight.dtype))
    return _bilinear(x1, x2, weight, bias)



@primitive("sequence_mask_op", nondiff=True)
def _sequence_mask(lengths, *, maxlen):
    return (jnp.arange(maxlen)[None, :] <
            lengths.reshape(-1, 1)).astype(jnp.int64).reshape(
        tuple(lengths.shape) + (maxlen,))


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    """[..., L] 0/1 mask from lengths (reference sequence_mask op)."""
    from ...ops import manipulation as _m

    if maxlen is None:
        import numpy as np

        maxlen = int(np.asarray(x.numpy()).max())
    out = _sequence_mask(x, maxlen=int(maxlen))
    return _m.cast(out, dtype)


def zeropad2d(x, padding, data_format="NCHW", name=None):
    return pad(x, padding, mode="constant", value=0.0,
               data_format=data_format)


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """Block-sparse attention (reference sparse_attention op, CUDA-only).

    TPU stance: XLA has no CSR attention lowering; the supported sparse
    pattern on TPU is blockwise flash attention (kernels/flash_attention) or
    ring attention for long context. Raises with that pointer."""
    raise ValueError(
        "sparse_attention's CSR kernel is CUDA-specific; on TPU use "
        "F.scaled_dot_product_attention (flash kernel) or "
        "distributed.context_parallel ring/ulysses attention")


def relu_(x, name=None):
    from .activation import relu

    out = relu(x)
    x._rebind(out)
    return x


def softmax_(x, axis=-1, dtype=None, name=None):
    from .activation import softmax

    out = softmax(x, axis)
    x._rebind(out)
    return x


def tanh_(x, name=None):
    from ...ops import math as _math

    out = _math.tanh(x)
    x._rebind(out)
    return x
