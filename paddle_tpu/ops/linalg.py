"""Linear algebra ops (paddle.tensor.linalg / paddle.linalg equivalents).

Matmuls are the MXU path: they stay un-decomposed single jax primitives so XLA
tiles them onto the systolic array directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import primitive
from ..core.tensor import Tensor


@primitive("matmul_v2")
def _matmul(x, y, *, transpose_x, transpose_y):
    if transpose_x:
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if transpose_y:
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    return jnp.matmul(x, y)


def matmul(x, y, transpose_x=False, transpose_y=False, name=None):
    return _matmul(x, y, transpose_x=bool(transpose_x), transpose_y=bool(transpose_y))


def bmm(x, y, name=None):
    return _matmul(x, y, transpose_x=False, transpose_y=False)


@primitive("dot_op")
def _dot(x, y):
    return jnp.sum(x * y, axis=-1)


def dot(x, y, name=None):
    return _dot(x, y)


def mm(input, mat2, name=None):
    return matmul(input, mat2)


def mv(x, vec, name=None):
    return matmul(x, vec)


@primitive("addmm_op")
def _addmm(input, x, y, *, beta, alpha):
    return beta * input + alpha * jnp.matmul(x, y)


def addmm(input, x, y, beta=1.0, alpha=1.0, name=None):
    return _addmm(input, x, y, beta=float(beta), alpha=float(alpha))


@primitive("outer_op")
def _outer(x, y):
    return jnp.outer(x, y)


def outer(x, y, name=None):
    return _outer(x, y)


@primitive("inner_op")
def _inner(x, y):
    return jnp.inner(x, y)


def inner(x, y, name=None):
    return _inner(x, y)


@primitive("einsum_op")
def _einsum(*ops, equation):
    return jnp.einsum(equation, *ops)


def einsum(equation, *operands):
    return _einsum(*operands, equation=equation)


@primitive("p_norm")
def _norm(x, *, p, axis, keepdim):
    if p == "fro" or (p == 2 and axis is None):
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=keepdim))
    if p == np.inf:
        return jnp.max(jnp.abs(x), axis=axis, keepdims=keepdim)
    if p == -np.inf:
        return jnp.min(jnp.abs(x), axis=axis, keepdims=keepdim)
    if p == 0:
        return jnp.sum((x != 0).astype(x.dtype), axis=axis, keepdims=keepdim)
    if p == 1:
        return jnp.sum(jnp.abs(x), axis=axis, keepdims=keepdim)
    return jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=keepdim), 1.0 / p)


def norm(x, p="fro", axis=None, keepdim=False, name=None):
    if isinstance(axis, (list, tuple)):
        axis = tuple(int(a) for a in axis)
    elif axis is not None:
        axis = int(axis)
    return _norm(x, p=p, axis=axis, keepdim=bool(keepdim))


@primitive("dist_op")
def _dist(x, y, *, p):
    d = jnp.abs(x - y)
    if p == 0:
        return jnp.sum((d != 0).astype(x.dtype))
    if p == np.inf:
        return jnp.max(d)
    if p == -np.inf:
        return jnp.min(d)
    return jnp.power(jnp.sum(jnp.power(d, p)), 1.0 / p)


def dist(x, y, p=2, name=None):
    return _dist(x, y, p=float(p))


# -- decompositions / solvers (jnp.linalg; differentiable through jax) -------

@primitive("cholesky_op")
def _cholesky(x, *, upper):
    L = jnp.linalg.cholesky(x)
    return jnp.swapaxes(L, -1, -2) if upper else L


def cholesky(x, upper=False, name=None):
    return _cholesky(x, upper=bool(upper))


@primitive("inverse_op")
def _inv(x):
    return jnp.linalg.inv(x)


def inv(x, name=None):
    return _inv(x)


inverse = inv


@primitive("qr_op")
def _qr(x, *, mode):
    q, r = jnp.linalg.qr(x, mode=mode)
    return q, r


def qr(x, mode="reduced", name=None):
    return _qr(x, mode=mode)


@primitive("svd_op")
def _svd(x, *, full_matrices):
    return tuple(jnp.linalg.svd(x, full_matrices=full_matrices))


def svd(x, full_matrices=False, name=None):
    return _svd(x, full_matrices=bool(full_matrices))


@primitive("eigh_op")
def _eigh(x, *, UPLO):
    w, v = jnp.linalg.eigh(x, UPLO=UPLO)
    return w, v


def eigh(x, UPLO="L", name=None):
    return _eigh(x, UPLO=UPLO)


@primitive("eigvalsh_op")
def _eigvalsh(x, *, UPLO):
    return jnp.linalg.eigvalsh(x, UPLO=UPLO)


def eigvalsh(x, UPLO="L", name=None):
    return _eigvalsh(x, UPLO=UPLO)


@primitive("solve_op")
def _solve(a, b):
    return jnp.linalg.solve(a, b)


def solve(x, y, name=None):
    return _solve(x, y)


@primitive("triangular_solve_op")
def _triangular_solve(a, b, *, upper, transpose, unitriangular):
    return jax.scipy.linalg.solve_triangular(
        a, b, lower=not upper, trans=1 if transpose else 0, unit_diagonal=unitriangular
    )


def triangular_solve(x, y, upper=True, transpose=False, unitriangular=False, name=None):
    return _triangular_solve(x, y, upper=bool(upper), transpose=bool(transpose), unitriangular=bool(unitriangular))


@primitive("cholesky_solve_op")
def _cholesky_solve(b, L, *, upper):
    return jax.scipy.linalg.cho_solve((L, not upper), b)


def cholesky_solve(x, y, upper=False, name=None):
    return _cholesky_solve(x, y, upper=bool(upper))


@primitive("matrix_power_op")
def _matrix_power(x, *, n):
    return jnp.linalg.matrix_power(x, n)


def matrix_power(x, n, name=None):
    return _matrix_power(x, n=int(n))


@primitive("matrix_rank_op", nondiff=True)
def _matrix_rank(x, *, tol):
    return jnp.linalg.matrix_rank(x, rtol=tol)


def matrix_rank(x, tol=None, hermitian=False, name=None):
    return _matrix_rank(x, tol=tol)


@primitive("pinv_op")
def _pinv(x, *, rcond):
    return jnp.linalg.pinv(x, rtol=rcond)


def pinv(x, rcond=1e-15, hermitian=False, name=None):
    return _pinv(x, rcond=float(rcond))


@primitive("det_op")
def _det(x):
    return jnp.linalg.det(x)


def det(x, name=None):
    return _det(x)


@primitive("slogdet_op")
def _slogdet(x):
    sign, logdet = jnp.linalg.slogdet(x)
    return jnp.stack([sign, logdet])


def slogdet(x, name=None):
    return _slogdet(x)


@primitive("lu_op")
def _lu(x):
    lu, piv = jax.scipy.linalg.lu_factor(x)
    return lu, piv.astype(jnp.int32)


def lu(x, pivot=True, get_infos=False, name=None):
    lu_, piv = _lu(x)
    if get_infos:
        from . import creation as _c

        return lu_, piv, _c.zeros([1], "int32")
    return lu_, piv


@primitive("cross_op")
def _cross(x, y, *, axis):
    return jnp.cross(x, y, axis=axis)


def cross(x, y, axis=9, name=None):
    if axis == 9:
        axis = next((i for i, s in enumerate(x.shape) if s == 3), -1)
    return _cross(x, y, axis=int(axis))


@primitive("histogram_op", nondiff=True)
def _histogram(x, *, bins, min, max):
    hist, _ = jnp.histogram(x, bins=bins, range=(min, max) if (min != 0 or max != 0) else None)
    return hist.astype(jnp.int32)


def histogram(input, bins=100, min=0, max=0, name=None):
    return _histogram(input, bins=int(bins), min=float(min), max=float(max))


@primitive("bincount_op", nondiff=True)
def _bincount(x, *, minlength):
    return jnp.bincount(x, minlength=minlength)


@primitive("bincount_weighted_op", nondiff=True)
def _bincount_w(x, weights, *, minlength):
    n = max(minlength, 1)
    out = jnp.zeros((n,), weights.dtype)
    out = out.at[x].add(weights)
    # grow to the true max bin if it exceeds minlength (static shape needed:
    # use the full possible range via length hint)
    return out


def bincount(x, weights=None, minlength=0, name=None):
    if weights is not None:
        import numpy as np
        import jax.core as jcore

        data = x.data if hasattr(x, "data") else x
        if isinstance(data, jcore.Tracer):
            # bin count must be static under XLA: inside a trace the caller
            # supplies it via minlength (the host-max derivation needs a
            # concrete value)
            if minlength <= 0:
                raise ValueError(
                    "bincount with weights under jit/to_static needs "
                    "minlength (> max(x)) — the output length cannot depend "
                    "on traced values")
            length = int(minlength)
        else:
            xv = np.asarray(data)
            length = int(max(int(xv.max()) + 1 if xv.size else 0, minlength))
        return _bincount_w(x, weights, minlength=length)
    return _bincount(x, minlength=int(minlength))


@primitive("corrcoef_op")
def _corrcoef(x, *, rowvar):
    return jnp.corrcoef(x, rowvar=rowvar)


def corrcoef(x, rowvar=True, name=None):
    return _corrcoef(x, rowvar=bool(rowvar))


@primitive("cov_op")
def _cov(x, *, rowvar, ddof):
    return jnp.cov(x, rowvar=rowvar, ddof=ddof)


def cov(x, rowvar=True, ddof=True, fweights=None, aweights=None, name=None):
    return _cov(x, rowvar=bool(rowvar), ddof=1 if ddof else 0)


@primitive("multi_dot_op")
def _multi_dot(*xs):
    return jnp.linalg.multi_dot(xs)


def multi_dot(x, name=None):
    return _multi_dot(*x)


@primitive("linalg_lstsq")
def _lstsq(a, b, *, rcond):
    sol, res, rank, sv = jnp.linalg.lstsq(a, b, rcond=rcond)
    return sol, res, rank.astype(jnp.int32), sv


def lstsq(x, y, rcond=None, driver=None, name=None):
    """Least squares (reference linalg.lstsq over gels)."""
    return _lstsq(x, y, rcond=rcond)


@primitive("linalg_cond")
def _cond(x, *, p):
    return jnp.linalg.cond(x, p=p)


def cond(x, p=None, name=None):
    """Condition number (reference linalg.cond)."""
    return _cond(x, p=p if p in (None, 1, -1, 2, -2) or isinstance(p, str)
                 else float(p))


def _host_eig(arr, vectors: bool):
    """General eig through host LAPACK as a ``jax.pure_callback``: XLA has
    no TPU lowering for it (``jnp.linalg.eig`` is CPU-only), but host
    callbacks and complex64 results work on the chip (probed on a v5e, PR
    21), so the op runs eagerly AND inside jit-traced code on any backend.
    Nondifferentiable."""
    import numpy as np

    cdtype = jnp.complex64 if arr.dtype in (jnp.float32, jnp.complex64) \
        else jnp.result_type(arr.dtype, jnp.complex64)
    vals = jax.ShapeDtypeStruct(arr.shape[:-1], cdtype)
    if not vectors:
        return jax.pure_callback(
            lambda a: np.linalg.eigvals(np.asarray(a)).astype(cdtype),
            vals, arr)

    def host(a):
        w, v = np.linalg.eig(np.asarray(a))
        return w.astype(cdtype), v.astype(cdtype)

    return jax.pure_callback(
        host, (vals, jax.ShapeDtypeStruct(arr.shape, cdtype)), arr)


def eig(x, name=None):
    """General (complex) eigendecomposition (host LAPACK; see
    ``_host_eig``). Use eigh for the symmetric case, which lowers
    natively."""
    from ..core.tensor import Tensor as _T

    vals, vecs = _host_eig(x.data if isinstance(x, _T) else jnp.asarray(x),
                           vectors=True)
    return _T(vals), _T(vecs)


@primitive("tensordot_op")
def _tensordot(x, y, *, axes):
    return jnp.tensordot(x, y, axes=axes)


def tensordot(x, y, axes=2, name=None):
    if isinstance(axes, (list, tuple)):
        axes = tuple(tuple(int(v) for v in a) if isinstance(a, (list, tuple))
                     else int(a) for a in axes)
    else:
        axes = int(axes)
    return _tensordot(x, y, axes=axes)


def eigvals(x, name=None):
    """General eigenvalues (host LAPACK; see ``_host_eig``)."""
    from ..core.tensor import Tensor as _T

    return _T(_host_eig(x.data if isinstance(x, _T) else jnp.asarray(x),
                        vectors=False))


@primitive("lu_unpack_op")
def _lu_unpack(lu_data, perm, *, unpack_ludata, unpack_pivots):
    n = lu_data.shape[-2]
    m = lu_data.shape[-1]
    k = min(n, m)
    L = jnp.tril(lu_data[..., :, :k], -1) + jnp.eye(n, k, dtype=lu_data.dtype)
    U = jnp.triu(lu_data[..., :k, :])
    # pivots -> permutation matrix (sequential row swaps, LAPACK ipiv style)
    P = jnp.eye(n, dtype=lu_data.dtype)
    def swap(P, i):
        j = perm[i]
        row_i, row_j = P[i], P[j]
        P = P.at[i].set(row_j).at[j].set(row_i)
        return P
    for i in range(perm.shape[-1]):
        P = swap(P, i)
    return P.T, L, U


def lu_unpack(x, y, unpack_ludata=True, unpack_pivots=True, name=None):
    """(P, L, U) from lu()'s packed output + pivots (reference lu_unpack).
    2-D inputs only — the pivot-walk below is unbatched."""
    if x.ndim != 2:
        raise ValueError(
            f"lu_unpack supports 2-D factors only (got ndim={x.ndim}); "
            "vmap over the batch for batched unpacking")
    return _lu_unpack(x, y, unpack_ludata=bool(unpack_ludata),
                      unpack_pivots=bool(unpack_pivots))
