"""The benchmark's own arithmetic, checked against hand-worked values: the
FLOPs a decoder requires, the traffic generators, the open loop's due-time
accounting, the percentile, and the reduction from a profiler trace to busy /
idle / exposed-collective / kernel time (on hand-made intervals and on the
small trace kept in ``benchmark/testdata/``)."""
import json
import os

import numpy as np
import pytest

from benchmark.lib import flops, peaks, stats, traffic, xplane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


BIG = dict(hidden_size=2048, intermediate_size=5632, num_hidden_layers=20,
           num_attention_heads=16, num_key_value_heads=16, vocab_size=32000)


# -- FLOPs per token -----------------------------------------------------------

@pytest.mark.parametrize("cfg,gflop", [
    # per layer: q 2048*2048 + k,v 2*2048*1024 + o 2048*2048 + SwiGLU
    # 3*2048*8192 = 62.91 M; x12 = 754.97 M; head 2048*92544 = 189.53 M;
    # 6 * 944.50 M = 5.667 G; causal attention 6*2048*2048*12 = 0.302 G
    ("internlm2-1.8b-d12", 5.969),
    # x24 layers: 6 * (1509.95 M + 189.53 M) = 10.197 G + 0.604 G
    ("internlm2-1.8b-x4", 10.801),
    # chip_smoke's `big`: 20 * 51.38 M + 65.54 M = 1093.14 M; x6 = 6.559 G;
    # + 6*2048*2048*20 = 0.503 G. The program's own function says 7.96.
    (BIG, 7.062),
])
def test_train_flops_per_token_hand_values(cfg, gflop):
    cfg = _config(cfg) if isinstance(cfg, str) else cfg
    got = flops.decoder_train_flops_per_token(cfg, 2048) / 1e9
    assert got == pytest.approx(gflop, abs=0.001)


def test_program_flops_function_counts_more_than_required():
    from paddle_tpu.models import LlamaConfig, llama_flops_per_token

    theirs = llama_flops_per_token(LlamaConfig(
        max_position_embeddings=2048, **BIG), 2048) / 1e9
    assert theirs == pytest.approx(7.96, abs=0.01)  # embedding + full attn
    assert flops.decoder_train_flops_per_token(BIG, 2048) / 1e9 < theirs


@pytest.mark.parametrize("name,params_b", [("internlm2-1.8b-d12", 1.134),
                                           ("internlm2-1.8b-x4", 1.889)])
def test_param_count(name, params_b):
    assert flops.decoder_param_count(_config(name)) / 1e9 == \
        pytest.approx(params_b, abs=0.001)


def test_mfu_and_peaks():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    # 16.5 k tokens/s * 5.969 GFLOP over one 197 TFLOP/s chip = 49.99 %
    assert flops.mfu_pct(5.969e9, 16500, 1, 197e12) == \
        pytest.approx(49.994, abs=0.01)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4


# -- traffic ---------------------------------------------------------------------

CHAT = {"kind": "open_loop", "rate_rps": 7.0,
        "prompt_len": {"median": 96, "sigma": 0.7, "min": 16, "max": 256},
        "output_len": {"median": 64, "sigma": 0.7, "min": 8, "max": 192}}


def _lens(reqs):
    return sorted(len(r.prompt) for r in reqs), \
        sorted(r.max_new for r in reqs)


def test_open_loop_same_seed_same_requests():
    a = traffic.open_loop_schedule(CHAT, 50257, 3_000_000_019, 30)
    b = traffic.open_loop_schedule(CHAT, 50257, 3_000_000_019, 30)
    assert len(a) == len(b) == 210
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        assert (x.prompt == y.prompt).all()


def test_open_loop_seed_changes_tokens_not_the_work():
    a = traffic.open_loop_schedule(CHAT, 50257, 1, 30)
    b = traffic.open_loop_schedule(CHAT, 50257, 2, 30)
    assert [r.due for r in a] == [r.due for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    # another mix order (the data file's order_seed) is another sequence of
    # the same multiset
    c = traffic.open_loop_schedule(dict(CHAT, order_seed=1), 50257, 1, 30)
    assert _lens(a) == _lens(c)
    assert [r.max_new for r in a] != [r.max_new for r in c]
    gaps = lambda rs: sorted(np.round(np.diff(  # noqa: E731
        [0.0] + [r.due for r in rs]), 9))
    assert gaps(a) == gaps(c)


def test_open_loop_lengths_clipped_and_due_in_window():
    reqs = traffic.open_loop_schedule(CHAT, 50257, 5, 30)
    p, o = _lens(reqs)
    assert p[0] >= 16 and p[-1] <= 256 and o[0] >= 8 and o[-1] <= 192
    assert p[0] == 16 and p[-1] == 256    # the tails really are clipped
    assert abs(np.median(p) - 96) <= 2 and abs(np.median(o) - 64) <= 2
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 30
    assert all(0 <= t < 50257 for r in reqs for t in r.prompt)


def test_open_loop_shared_prefix_and_bursts_are_parameters():
    mix = dict(CHAT, shared_prefix={"share": 0.8, "tokens": 192,
                                    "n_prefixes": 1},
               prompt_len={"median": 40, "sigma": 0.5, "min": 8, "max": 64})
    mix["prompt_len"]["max"] = 256
    reqs = traffic.open_loop_schedule(mix, 50257, 9, 20)
    heads = [tuple(r.prompt[:192]) for r in reqs if len(r.prompt) > 192]
    assert len(heads) == round(0.8 * len(reqs)) and len(set(heads)) == 1
    burst = dict(CHAT, bursts={"on_s": 2.0, "off_s": 3.0})
    reqs = traffic.open_loop_schedule(burst, 50257, 9, 30)
    assert len(reqs) == 210                       # the same mean rate
    assert all((r.due % 5.0) <= 2.0 + 1e-9 for r in reqs)  # only in `on`


def test_packed_documents():
    mix = {"batch": 2, "seq": 64, "eos_token": 2,
           "doc_len": {"median": 20, "sigma": 1.0, "min": 4, "max": 100}}
    a = traffic.packed_documents(mix, 512, 3_000_000_019)
    b = traffic.packed_documents(mix, 512, 3_000_000_019)
    first = next(a)
    assert first.shape == (2, 64) and first.dtype == np.int64
    assert (first == next(b)).all() and (next(a) == next(b)).all()
    other = next(traffic.packed_documents(mix, 512, 4))
    assert (first != other).any()
    many = np.concatenate([next(a).ravel() for _ in range(50)])
    assert many.min() >= 0 and many.max() < 512
    assert (many == 2).mean() > 0.02              # documents end in EOS
    # Zipf: the low ids are far more frequent than the high ones
    assert (many < 16).mean() > 3 * (many >= 256).mean()


# -- the open loop's accounting --------------------------------------------------

def test_open_loop_times_from_due_not_from_send():
    """A server that stalls 0.5 s on the second request makes the third
    LATE; its lateness is on the generator's account and its latency runs
    from when it was due. Nothing is ever sent early."""
    clock = {"t": 100.0}
    reqs = [traffic.Request(i, due, np.zeros(1, np.int64), 1)
            for i, due in enumerate([0.0, 0.1, 0.2, 1.0])]
    sent = []

    def send(r):
        sent.append((r.index, clock["t"]))
        if r.index == 1:
            clock["t"] += 0.5   # the call blocks: the server stalls us

    def sleep(dt):
        assert dt > 0
        clock["t"] += dt

    t0 = traffic.run_open_loop(reqs, send, now=lambda: clock["t"],
                               sleep=sleep)
    assert t0 == 100.0
    late = [r.t_send - (t0 + r.due) for r in reqs]
    assert late == pytest.approx([0.0, 0.0, 0.4, 0.0])
    assert all(x >= 0 for x in late)
    assert [i for i, _ in sent] == [0, 1, 2, 3]


# -- trace reduction -------------------------------------------------------------

def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == \
        [(0, 3), (5, 7)]
    assert xplane.total([(0, 3), (5, 7)]) == 5
    assert xplane.gaps([(0, 3), (5, 7)], 0, 10) == [(3, 5), (7, 10)]
    assert xplane.gaps([], 0, 4) == [(0, 4)]


def test_leaf_segments_give_each_instant_to_the_innermost_op():
    evs = [("while", 0, 10), ("a", 0, 4), ("all-reduce.1", 4, 6),
           ("b", 7, 9), ("c", 12, 13)]
    assert xplane.leaf_segments(evs) == [
        ("a", 0, 4), ("all-reduce.1", 4, 6), ("while", 6, 7), ("b", 7, 9),
        ("while", 9, 10), ("c", 12, 13)]


def test_names_as_the_v5e_trace_gives_them():
    """An ops-line event is named by its whole HLO text (seen by hand in a
    real v5e trace, PR 23)."""
    t = "bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)S(1)}"
    fusion = (f"%fusion.383 = {t} fusion(bf16[2048,8192]{{1,0:T(8,128)(2,1)"
              f"S(1)}} %copy-done.14), kind=kOutput, calls=%fused.51")
    kernel = ("%_unknown_.135 = (bf16[64,2048,128]{2,1,0:T(8,128)(2,1)}, "
              "bf16[64,2048,128]{2,1,0:T(8,128)(2,1)}) custom-call(s32[1]"
              "{0:T(128)} %get-tuple-element.1458), custom_call_target="
              "\"tpu_custom_call\", frontend_attributes={kernel_metadata={}}")
    ar = (f"%all-reduce-start.12 = {t} all-reduce-start({t} %fusion.3), "
          f"replica_groups={{{{0,1}}}}")
    assert xplane.short_name(fusion) == "fusion.383"
    assert xplane.opcode(fusion) == "fusion"
    assert xplane.opcode(kernel) == "custom-call"
    assert xplane.is_mosaic_kernel(kernel) and not xplane.is_mosaic_kernel(
        fusion)
    assert xplane.label(kernel).startswith(
        "_unknown_.135 custom-call tpu_custom_call (bf16[64,2048,128]")
    assert xplane.label(fusion).startswith("fusion.383 fusion bf16[4,2048")
    assert len(xplane.label(kernel)) <= 96
    assert xplane.is_collective(ar)
    for n in ("all-reduce.3", "all-gather-start.12", "all-reduce-done",
              "reduce-scatter.1", "collective-permute-done.4", "all-to-all"):
        assert xplane.is_collective(n), n
    for n in ("fusion.3", "all-reduce-fusion-not", "while.1", "copy.2",
              fusion, kernel):
        assert not xplane.is_collective(n), n


def test_summary_of_the_small_recorded_trace():
    path = os.path.join(BENCH, "testdata", "small.xplane.pb")
    planes = xplane.read_xplane(path)
    assert set(planes) == {"/host:CPU", "/device:TPU:0", "/device:TPU:1"}
    s = xplane.summarize(planes)
    # window = the bench.window span: 1 us .. 11 us
    assert s["window_s"] == pytest.approx(10e-6)
    assert s["devices"] == 2
    # device 0 busy [2,9] + [9.5,10] = 7.5 us (the async copy that spans
    # 1.5 .. 9.8 us is not an op running); device 1 busy [2,9] = 7 us
    assert s["per_device_busy_s"][0] == pytest.approx(7.5e-6)
    assert s["per_device_busy_s"][1] == pytest.approx(7.0e-6)
    assert s["busy_s"] == pytest.approx(7.25e-6)
    # innermost-op time in a collective: 1 us and 1.5 us
    assert s["collective_exposed_s"] == pytest.approx(1.25e-6)
    # the Mosaic call runs 1 us on device 0 only
    assert s["kernel_s"] == pytest.approx(0.5e-6)
    ops = {n.split()[0]: t for n, t in s["device_ops"]}  # self time, summed
    assert ops["fusion.2"] == pytest.approx(5.5e-6)
    assert ops["fusion.1"] == pytest.approx(4.0e-6)
    assert ops["all-reduce.3"] == pytest.approx(2.5e-6)
    assert ops["while.1"] == pytest.approx(1.0e-6)   # only its own gaps
    outer = {n.split()[0]: t for n, t in s["outermost_ops"]}
    assert outer["while.1"] == pytest.approx(7.0e-6)  # the whole loop
    assert "_unknown_.7" not in outer                 # it is inside the loop
    # idle gaps of device 0, longest first, named by the host's span
    assert s["idle_gaps"] == [["bench.next_batch", pytest.approx(1e-6)],
                              ["bench.block", pytest.approx(1e-6)],
                              ["bench.block", pytest.approx(0.5e-6)]]


def test_summary_of_one_real_v5e_train_step():
    """``v5e_train_step.xplane.pb``: one step of cell 1 as recorded on the
    chip in PR 23 (cut down by ``benchmark/testdata/trim_trace.py``)."""
    path = os.path.join(BENCH, "testdata", "v5e_train_step.xplane.pb")
    planes = xplane.read_xplane(path)
    ops = planes["/device:TPU:0"]["XLA Ops"]
    assert len(ops) > 3000
    kernels = {xplane.short_name(n) for n, _s, _e in ops
               if xplane.is_mosaic_kernel(n)}
    assert len(kernels) == 18          # the 18 tpu_custom_call of the step
    assert not any(xplane.is_collective(n) for n, _s, _e in ops)
    calls = [e for e in xplane.host_spans(planes)
             if e[0] == "bench.step_call"]
    blocks = [e for e in xplane.host_spans(planes) if e[0] == "bench.block"]
    s = xplane.summarize(planes, window=(calls[0][1], blocks[0][2]))
    assert s["window_s"] == pytest.approx(0.4711, abs=1e-3)
    assert s["busy_s"] == pytest.approx(0.4675, abs=1e-3)
    assert s["collective_exposed_s"] == 0
    assert 100 * s["kernel_s"] / s["busy_s"] == pytest.approx(16.2, abs=0.2)
    outer = [n.split()[0] for n, _t in s["outermost_ops"][:4]]
    assert all(n.startswith("while.") for n in outer)   # the four loops
    # the host dispatches, then waits: the idle before the first op is the
    # dispatch, and what the host does in the longest gap is named
    assert s["idle_gaps"][0][0] in ("bench.step_call", "bench.next_batch",
                                    "bench.block")


def test_summary_without_a_device_plane_is_nothing():
    assert xplane.summarize({"/host:CPU": {"python3": [
        ("bench.window", 0.0, 10.0)]}}) is None
