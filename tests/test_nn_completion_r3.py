"""Round-3 nn/nn.functional surface completion: 1D/3D families, unpool,
losses, beam search — numpy-oracle checks."""
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def _t(a, dt="float32"):
    return paddle.to_tensor(np.asarray(a, dt))


REFERENCE = "/root/reference/python/paddle"


def test_nn_and_functional_export_parity():
    if not os.path.isdir(REFERENCE):
        pytest.skip(f"the reference snapshot is not mounted: {REFERENCE} "
                    "is absent, and its export lists come from nowhere else")
    for sub, refpath in [
            ("nn", f"{REFERENCE}/nn/__init__.py"),
            ("nn.functional", f"{REFERENCE}/nn/functional/__init__.py")]:
        ref = open(refpath).read()
        ref_names = set(re.findall(r"'(\w+)',?\s*(?:#.*)?$", ref, re.M))
        mod = paddle
        for part in sub.split("."):
            mod = getattr(mod, part)
        missing = sorted(n for n in ref_names - set(dir(mod))
                         if not n.startswith("_"))
        assert not missing, f"{sub} missing: {missing}"


class TestPool13D:
    def test_max_avg_pool1d(self):
        x = np.arange(8, dtype="float32").reshape(1, 1, 8)
        np.testing.assert_allclose(
            F.max_pool1d(_t(x), 2, 2).numpy().ravel(), [1, 3, 5, 7])
        np.testing.assert_allclose(
            F.avg_pool1d(_t(x), 2, 2).numpy().ravel(), [0.5, 2.5, 4.5, 6.5])

    def test_pool3d(self):
        x = np.arange(16, dtype="float32").reshape(1, 1, 2, 2, 4)
        out = F.max_pool3d(_t(x), (2, 2, 2), (2, 2, 2))
        np.testing.assert_allclose(out.numpy().ravel(), [13, 15])
        avg = F.avg_pool3d(_t(x), (2, 2, 2), (2, 2, 2))
        np.testing.assert_allclose(avg.numpy().ravel(),
                                   [x.ravel()[[0,1,4,5,8,9,12,13]].mean(),
                                    x.ravel()[[2,3,6,7,10,11,14,15]].mean()])

    def test_adaptive_1d_3d(self):
        x = np.arange(12, dtype="float32").reshape(1, 1, 12)
        np.testing.assert_allclose(
            F.adaptive_avg_pool1d(_t(x), 3).numpy().ravel(),
            [x[0, 0, :4].mean(), x[0, 0, 4:8].mean(), x[0, 0, 8:].mean()])
        y = np.random.RandomState(0).rand(1, 2, 4, 4, 4).astype("float32")
        out = F.adaptive_avg_pool3d(_t(y), 2)
        np.testing.assert_allclose(
            out.numpy(), y.reshape(1, 2, 2, 2, 2, 2, 2, 2).mean((3, 5, 7)),
            rtol=1e-6)

    def test_unpool2d_inverts_pool(self):
        x = np.random.RandomState(1).rand(1, 1, 4, 4).astype("float32")
        out, mask = F.max_pool2d(_t(x), 2, 2, return_mask=True)
        rec = F.max_unpool2d(out, mask, 2, 2)
        # every pooled max lands back at its original location
        ref = np.zeros_like(x)
        for i in range(2):
            for j in range(2):
                win = x[0, 0, 2*i:2*i+2, 2*j:2*j+2]
                yy, xx = np.unravel_index(win.argmax(), win.shape)
                ref[0, 0, 2*i+yy, 2*j+xx] = win.max()
        np.testing.assert_allclose(rec.numpy(), ref, rtol=1e-6)


class TestConv13D:
    def test_conv3d_matches_manual(self):
        x = np.random.RandomState(2).rand(1, 1, 3, 3, 3).astype("float32")
        w = np.ones((1, 1, 3, 3, 3), "float32")
        out = F.conv3d(_t(x), _t(w))
        np.testing.assert_allclose(float(out.numpy().ravel()[0]),
                                   x.sum(), rtol=1e-5)

    def test_conv1d_transpose_shape_and_grad(self):
        paddle.seed(0)
        layer = nn.Conv1DTranspose(3, 5, 4, stride=2)
        x = paddle.randn([2, 3, 8])
        out = layer(x)
        assert out.shape == [2, 5, 18]
        out.sum().backward()
        assert layer.weight.grad is not None

    def test_conv3d_transpose_shape(self):
        paddle.seed(0)
        layer = nn.Conv3DTranspose(2, 4, 3, stride=2)
        out = layer(paddle.randn([1, 2, 4, 4, 4]))
        assert out.shape == [1, 4, 9, 9, 9]


class TestLosses:
    def test_ctc_loss_matches_known(self):
        # trivially separable case: correct path dominates -> small loss
        T, B, K = 4, 1, 3
        logits = np.full((T, B, K), -10.0, "float32")
        for t, c in enumerate([1, 1, 2, 2]):
            logits[t, 0, c] = 10.0
        labels = np.array([[1, 2]], "int64")
        loss = F.ctc_loss(_t(logits), _t(labels, "int64"),
                          _t([4], "int64"), _t([2], "int64"),
                          reduction="none")
        assert float(loss.numpy()[0]) < 1.0

    def test_dice_log_label_smooth(self):
        probs = np.array([[0.9, 0.1], [0.2, 0.8]], "float32")
        lab = np.array([[0], [1]], "int64")
        d = F.dice_loss(_t(probs), _t(lab, "int64"))
        assert 0.0 < float(d) < 0.5
        ll = F.log_loss(_t([0.9]), _t([1.0]))
        np.testing.assert_allclose(float(ll), -np.log(0.9 + 1e-4), rtol=1e-4)
        sm = F.label_smooth(_t([[0.0, 1.0]]), epsilon=0.1)
        np.testing.assert_allclose(sm.numpy(), [[0.05, 0.95]], rtol=1e-5)

    def test_hsigmoid_loss_trains(self):
        paddle.seed(0)
        layer = nn.HSigmoidLoss(8, 6)
        x = paddle.randn([4, 8])
        lab = _t([0, 1, 2, 3], "int64")
        loss = layer(x, lab)
        assert np.isfinite(float(loss))
        loss.backward()
        assert layer.weight.grad is not None

    def test_margin_cross_entropy(self):
        paddle.seed(1)
        cosines = np.array([[0.9, 0.1], [0.2, 0.8]], "float32")
        lab = np.array([0, 1], "int64")
        plain = F.margin_cross_entropy(_t(cosines), _t(lab, "int64"),
                                       margin1=1.0, margin2=0.0, margin3=0.0,
                                       scale=1.0)
        # with zero margins and scale 1 this IS softmax CE on the cosines
        ref = -np.log(np.exp(cosines[[0, 1], [0, 1]]) /
                      np.exp(cosines).sum(1)).mean()
        np.testing.assert_allclose(float(plain), ref, rtol=1e-5)

    def test_sigmoid_focal_and_npair(self):
        logit = _t([[2.0, -2.0]])
        label = _t([[1.0, 0.0]])
        fl = F.sigmoid_focal_loss(logit, label)
        assert float(fl) < 0.1
        a = _t(np.eye(2, 4, dtype="float32"))
        p = _t(np.eye(2, 4, dtype="float32"))
        nl = F.npair_loss(a, p, _t([0, 1], "int64"))
        assert np.isfinite(float(nl))


class TestMisc:
    def test_sequence_mask(self):
        m = F.sequence_mask(_t([2, 3], "int64"), maxlen=4)
        np.testing.assert_array_equal(m.numpy(),
                                      [[1, 1, 0, 0], [1, 1, 1, 0]])

    def test_temporal_shift_shapes(self):
        x = np.random.RandomState(3).rand(4, 8, 2, 2).astype("float32")
        out = F.temporal_shift(_t(x), seg_num=2, shift_ratio=0.25)
        assert out.shape == [4, 8, 2, 2]
        # last-half channels pass through unshifted
        np.testing.assert_allclose(out.numpy()[:, 4:], x[:, 4:])

    def test_local_response_norm(self):
        x = np.ones((1, 4, 2, 2), "float32")
        out = F.local_response_norm(_t(x), size=3, alpha=1.0, beta=1.0, k=0.0)
        assert np.isfinite(out.numpy()).all()

    def test_bilinear(self):
        x1 = _t([[1.0, 2.0]])
        x2 = _t([[3.0, 4.0]])
        w = _t(np.ones((1, 2, 2), "float32"))
        out = F.bilinear(x1, x2, w)
        np.testing.assert_allclose(float(out), (1 + 2) * (3 + 4))

    def test_inplace_functional(self):
        x = _t([-1.0, 2.0])
        F.relu_(x)
        np.testing.assert_allclose(x.numpy(), [0.0, 2.0])

    def test_beam_search_decoder_greedy_path(self):
        paddle.seed(0)

        class ToyCell(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 5)

            def forward(self, inp, state):
                return self.fc(state), state

        cell = ToyCell()
        emb = nn.Embedding(5, 4)
        dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=4,
                                   beam_size=2, embedding_fn=emb,
                                   output_fn=None)
        state = paddle.randn([2, 4])
        ids, scores = nn.dynamic_decode(dec, state, max_step_num=3)
        assert ids.shape[0] == 2 and ids.shape[1] == 2
        assert scores.shape == [2, 2]

    def test_new_layer_classes_smoke(self):
        paddle.seed(0)
        assert nn.MaxPool1D(2)(paddle.randn([1, 2, 8])).shape == [1, 2, 4]
        assert nn.AvgPool3D(2)(paddle.randn([1, 2, 4, 4, 4])).shape == \
            [1, 2, 2, 2, 2]
        assert nn.Pad1D([1, 1])(paddle.randn([1, 2, 4])).shape == [1, 2, 6]
        assert nn.ZeroPad2D([1, 1, 1, 1])(
            paddle.randn([1, 2, 3, 3])).shape == [1, 2, 5, 5]
        d3 = nn.Dropout3D(0.5)
        d3.eval()
        x = paddle.randn([1, 2, 2, 2, 2])
        np.testing.assert_allclose(d3(x).numpy(), x.numpy())
        up = nn.UpsamplingNearest2D(scale_factor=2)
        assert up(paddle.randn([1, 1, 3, 3])).shape == [1, 1, 6, 6]
        assert nn.InstanceNorm3D(2)(
            paddle.randn([1, 2, 2, 2, 2])).shape == [1, 2, 2, 2, 2]
