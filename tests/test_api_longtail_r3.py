"""Round-3 top-level API long tail: every reference paddle.* export exists
and the new ops match numpy oracles."""
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle


def _t(a, dt="float32"):
    return paddle.to_tensor(np.asarray(a, dt))


REFERENCE = "/root/reference/python/paddle"


def test_reference_toplevel_export_parity():
    if not os.path.isdir(REFERENCE):
        pytest.skip(f"the reference snapshot is not mounted: {REFERENCE} "
                    "is absent, and its export list comes from nowhere else")
    ref = open(f"{REFERENCE}/__init__.py").read()
    ref_names = set(re.findall(r"^\s+'(\w+)',\s*$", ref, re.M))
    ours = set(dir(paddle))
    missing = sorted(n for n in ref_names - ours if not n.startswith("_"))
    assert not missing, f"top-level exports missing vs reference: {missing}"


class TestNewOps:
    def test_diagonal(self):
        x = np.arange(12, dtype="float32").reshape(3, 4)
        np.testing.assert_allclose(paddle.diagonal(_t(x)).numpy(),
                                   np.diagonal(x))
        np.testing.assert_allclose(
            paddle.diagonal(_t(x), offset=1).numpy(), np.diagonal(x, 1))

    def test_kthvalue(self):
        x = np.array([[3.0, 1.0, 2.0], [9.0, 7.0, 8.0]], "float32")
        v, i = paddle.kthvalue(_t(x), 2)
        np.testing.assert_allclose(v.numpy(), [2.0, 8.0])
        np.testing.assert_allclose(i.numpy(), [2, 2])

    def test_mode(self):
        x = np.array([[1.0, 2.0, 2.0, 3.0], [5.0, 5.0, 4.0, 5.0]], "float32")
        v, i = paddle.mode(_t(x))
        np.testing.assert_allclose(v.numpy(), [2.0, 5.0])
        np.testing.assert_allclose(i.numpy(), [2, 3])  # last occurrence

    def test_multiplex(self):
        a = np.array([[1.0, 2.0], [3.0, 4.0]], "float32")
        b = np.array([[10.0, 20.0], [30.0, 40.0]], "float32")
        idx = np.array([[1], [0]], "int32")
        out = paddle.multiplex([_t(a), _t(b)], _t(idx, "int32"))
        np.testing.assert_allclose(out.numpy(), [[10.0, 20.0], [3.0, 4.0]])

    def test_scatter_nd(self):
        idx = np.array([[1], [3]], "int64")
        upd = np.array([9.0, 10.0], "float32")
        out = paddle.scatter_nd(_t(idx, "int64"), _t(upd), [5])
        np.testing.assert_allclose(out.numpy(), [0, 9, 0, 10, 0])

    def test_strided_slice(self):
        x = np.arange(24, dtype="float32").reshape(4, 6)
        out = paddle.strided_slice(_t(x), axes=[0, 1], starts=[0, 1],
                                   ends=[4, 6], strides=[2, 2])
        np.testing.assert_allclose(out.numpy(), x[0:4:2, 1:6:2])

    def test_unstack(self):
        x = np.arange(6, dtype="float32").reshape(3, 2)
        outs = paddle.unstack(_t(x), axis=0)
        assert len(outs) == 3
        np.testing.assert_allclose(outs[1].numpy(), x[1])

    def test_crop(self):
        x = np.arange(24, dtype="float32").reshape(4, 6)
        out = paddle.crop(_t(x), shape=[2, 3], offsets=[1, 2])
        np.testing.assert_allclose(out.numpy(), x[1:3, 2:5])
        out2 = paddle.crop(_t(x), shape=[-1, 2], offsets=[2, 0])
        np.testing.assert_allclose(out2.numpy(), x[2:, 0:2])

    def test_reverse_increment(self):
        x = np.array([1.0, 2.0, 3.0], "float32")
        np.testing.assert_allclose(paddle.reverse(_t(x), 0).numpy(),
                                   [3.0, 2.0, 1.0])
        np.testing.assert_allclose(paddle.increment(_t(x), 2.0).numpy(),
                                   [3.0, 4.0, 5.0])

    def test_renorm(self):
        x = np.array([[3.0, 4.0], [0.3, 0.4]], "float32")
        out = paddle.renorm(_t(x), p=2.0, axis=0, max_norm=1.0)
        norms = np.linalg.norm(out.numpy(), axis=1)
        assert norms[0] <= 1.0 + 1e-5
        np.testing.assert_allclose(out.numpy()[1], x[1], rtol=1e-5)

    def test_randint_like_poisson(self):
        x = _t(np.zeros((3, 4)), "float32")
        r = paddle.randint_like(x, 0, 10, dtype="int64")
        assert r.shape == [3, 4]
        assert int(r.numpy().min()) >= 0 and int(r.numpy().max()) < 10
        lam = _t(np.full((1000,), 4.0))
        p = paddle.poisson(lam)
        assert abs(float(p.numpy().mean()) - 4.0) < 0.5

    def test_shape_rank_and_checks(self):
        x = _t(np.zeros((2, 5)))
        np.testing.assert_array_equal(paddle.shape(x).numpy(), [2, 5])
        assert int(paddle.rank(x)) == 2
        assert paddle.broadcast_shape([2, 1, 3], [4, 3]) == [2, 4, 3]
        assert paddle.is_floating_point(x)
        assert not paddle.is_integer(x)
        assert not paddle.is_complex(x)
        with pytest.raises(ValueError):
            paddle.check_shape([2, 0, 3])

    def test_create_parameter(self):
        p = paddle.create_parameter([4, 8], "float32")
        assert p.shape == [4, 8] and not p.stop_gradient
        b = paddle.create_parameter([8], "float32", is_bias=True)
        np.testing.assert_allclose(b.numpy(), np.zeros(8))

    def test_module_inplace_aliases(self):
        x = _t(np.array([[1.0, 2.0], [3.0, 4.0]]))
        paddle.reshape_(x, [4])
        assert x.shape == [4]
        y = _t(np.array([0.5]))
        paddle.tanh_(y)
        np.testing.assert_allclose(y.numpy(), np.tanh([0.5]), rtol=1e-6)

    def test_batch_reader(self):
        def reader():
            return iter(range(7))

        batches = list(paddle.batch(reader, 3)())
        assert batches == [[0, 1, 2], [3, 4, 5], [6]]
        batches = list(paddle.batch(reader, 3, drop_last=True)())
        assert batches == [[0, 1, 2], [3, 4, 5]]
