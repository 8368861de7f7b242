"""The benchmark's arithmetic for the cell whose cache is of two layer kinds
(``benchmark/lib/kv_attention_cost.py``), its three readers on a cut trace,
the ``serve_window`` runner's shapes and check lengths, the configuration's
file against the published keys, and the waiting ``gpt2-large.burst`` cell's
data."""
import json
import os

import numpy as np
import pytest

from benchmark.lib import (harness, kv_attention_cost, peaks, program_trace,
                           traffic, xplane)
from benchmark.runners import serve_window

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
V5E = peaks.peaks_for("TPU v5 lite")
CELL = "laguna-xs2-d5.mixed-context-peak"
RANGED = {"kv_heads": 8, "head_dim": 128, "itemsize": 2, "window": 512,
          "layers": {"full": {"count": 2, "heads": 96},
                     "window": {"count": 3, "heads": 192}}}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_a_cached_key_is_4096_bytes_and_a_decode_round_is_bound_by_bytes():
    assert kv_attention_cost.key_bytes(RANGED) == 4096
    # a window layer's round: 128 rows of 512 keys in range, 64 heads
    one = dict(RANGED, heads_a_layer=64)
    cost = kv_attention_cost.decode_cost(128 * 512, 128 * 64, one)
    assert cost["bytes"] == 65536 * 4096 + 2 * 8192 * 128 * 2
    assert cost["flops"] == 4 * 128 * 65536 * 64
    floor = kv_attention_cost.floor_seconds(cost, V5E)
    assert floor["bound"] == "bytes"
    assert floor["seconds"] == pytest.approx(0.3329e-3, rel=1e-3)
    # 8 FLOP a cached byte: far under the ridge of 240
    assert cost["flops"] / (65536 * 4096) == 8


def test_a_prefill_chunk_is_bound_by_its_operations_and_kinds_add_up():
    # a 2048-token chunk at offset 2048 in a full layer of 48 heads
    keys = 2048 * 2048 + 2048 * 2049 // 2
    one = dict(RANGED, heads_a_layer=48)
    cost = kv_attention_cost.prefill_cost(keys, one)
    assert cost == {"bytes": 0, "flops": 4 * 128 * keys * 48}
    assert kv_attention_cost.floor_seconds(cost, V5E)["seconds"] == \
        pytest.approx(0.7849e-3, rel=1e-3)
    traced = dict(RANGED, traced={
        "full": {"keys_decode": 0, "keys_prefill": 2 * keys},
        "window": {"keys_decode": 3 * 128 * 512, "keys_prefill": 0},
        "rows_decode": 128})
    # the full layers' idle decode rows still move their queries and contexts
    idle = 128 * 96 * 2 * 128 * 2 / V5E["hbm_bytes_per_s"]
    assert kv_attention_cost.traced_floor_seconds(traced, V5E) == \
        pytest.approx(2 * 0.7849e-3 + 3 * 0.3329e-3 + idle, rel=1e-3)


def _reader(name):
    return harness.read_layer_metric(name)


def test_the_three_readers_on_a_cut_trace(monkeypatch):
    """Two window calls, one full call and a fusion inside ``bench.window``:
    the share is their self time over busy, the roofline the floor over
    their time, and the window's share of keys comes from the counters."""
    K = ('%{}.{} = bf16[4,8]{{1,0}} custom-call(bf16[4,8]{{1,0}} %p.1), '
         'custom_call_target="tpu_custom_call"')
    F = "%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p.2), kind=kLoop"
    pt = program_trace.ProgramTrace({
        xplane.HOST_PLANE: {"python3": [("bench.window", 0, 1_000_000)]},
        "/device:TPU:0": {xplane.OPS_LINE: [
            (K.format("pt_ranged_attention_window", 3), 0, 200_000),
            (K.format("pt_ranged_attention_full", 4), 250_000, 650_000),
            (F, 650_000, 750_000),
            (K.format("pt_ranged_attention_window", 5), 800_000, 900_000),
            (K.format("gmm", 6), 900_000, 950_000)]}})
    monkeypatch.setattr(program_trace, "current", lambda shapes, kind: pt)
    import jax

    monkeypatch.setattr(jax.devices()[0].__class__, "device_kind",
                        "TPU v5 lite", raising=False)
    keys = 2048 * 2048 + 2048 * 2049 // 2
    shapes = {"kind": "serve", "ranged": dict(RANGED, traced={
        "full": {"keys_decode": 0, "keys_prefill": 2 * keys},
        "window": {"keys_decode": 3 * 128 * 512, "keys_prefill": 0},
        "rows_decode": 128})}
    share = _reader("serve.ranged_attention_share_pct").reduce(
        None, {}, {}, shapes)
    assert share == pytest.approx(100 * 700 / 850)
    roof = _reader("serve.ranged_attention_roofline_pct").reduce(
        None, {}, {}, shapes)
    floor = 2 * 0.7849e-3 + 3 * 0.3329e-3 + \
        128 * 96 * 2 * 128 * 2 / V5E["hbm_bytes_per_s"]
    assert roof == pytest.approx(100 * floor / 700e-6, rel=1e-3)
    counters = {"attn_keys_full_total": 2 * 1000, "attn_keys_window_total":
                3 * 150}
    assert _reader("serve.window_keys_pct").reduce(
        None, counters, {}, shapes) == pytest.approx(15.0)


@pytest.mark.parametrize("name", [
    "serve.ranged_attention_share_pct", "serve.ranged_attention_roofline_pct",
    "serve.window_keys_pct"])
def test_a_reader_finds_nothing_in_a_program_that_lacks_it(name):
    """The parent has no such kernel or counter: the reader returns ``None``
    and does not raise (a train cell's shapes, a serve cell of another
    model, an untraced run of this one)."""
    mod = _reader(name)
    for shapes in ({"kind": "train"}, {"kind": "serve", "chips": 1}, {},
                   {"kind": "serve", "ranged": dict(RANGED, traced=None)}):
        assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_runners_shapes_split_the_keys_by_kind():
    spec = harness.Spec(CELL)
    assert serve_window._kernel_shapes(spec, {})["ranged"]["traced"] is None
    traced = dict.fromkeys(serve_window._WINDOW_COUNTERS, 0)
    traced.update(attn_keys_decode_total=1000, attn_keys_prefill_total=5000,
                  attn_keys_window_total=2100,
                  attn_keys_window_decode_total=900, slot_rounds=40,
                  moe_held_pairs_total=64, moe_experts_hit_total=48)
    got = serve_window._kernel_shapes(spec, traced)
    assert got["ranged"]["layers"] == RANGED["layers"]
    assert got["ranged"]["traced"] == {
        "full": {"keys_decode": 2000, "keys_prefill": 10000},
        "window": {"keys_decode": 900, "keys_prefill": 1200},
        "rows_decode": 40}
    assert got["moe"] == {"hidden": 2048, "width": 512, "itemsize": 2,
                          "traced": {"rows": 64, "experts_hit": 48}}


def test_two_of_the_checked_requests_have_prompts_past_8192():
    tr = _load("workloads", CELL + ".json")["traffic"]
    for seed in (0, 7, 3400000011):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 99]))
        lens = serve_window._check_lengths(128, tr, 16, 8192, rng)
        checked = [p for p, _o in lens[::16]]
        assert sum(p > 8192 for p in checked) >= 2 and max(checked) == 15360
        assert sorted(p for p, _o in lens) == sorted(
            traffic.lognormal_quantiles(128, tr["prompt_len"]).tolist())


def test_the_configuration_holds_every_published_key_unchanged():
    cfg = _load("configs", "laguna-xs2-d5.json")
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            want = 5 if key == "num_hidden_layers" else value
            assert cfg[key] == want, key
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert (cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["sliding_window"],
            cfg["vocab_size"]) == (2048, 8, 128, 8192, 256, 8, 512, 512, 512,
                                   100352)
    assert cfg["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert len(cfg["assumed"]) >= 8 and "pipeline stages" in cfg["deployment"]
    e = cfg["system"]["engine"]
    assert (e["max_slots"], e["max_seq_len"], e["page_len"]) == \
        (128, 16384, 128)
    # the window pool: 6 pages a decoding slot, three 2048-token chunks' worth
    assert e["window_pages"] == 128 * 6 + 3 * 21 + 1


def test_the_burst_cell_is_chat_steady_in_bursts():
    steady = _load("workloads", "gpt2-large.chat-steady.json")
    path = os.path.join(BENCH, "workloads", "gpt2-large.burst.json")
    if not os.path.exists(path):
        pytest.skip("gpt2-large.burst was not admitted (PERF.md section 7)")
    burst = _load("workloads", "gpt2-large.burst.json")
    assert {k: v for k, v in burst["traffic"].items() if k != "bursts"} == \
        {k: v for k, v in steady["traffic"].items() if k != "bursts"}
    assert burst["traffic"]["bursts"] == {"on_s": 5, "off_s": 5}
    a = traffic.open_loop_schedule(steady["traffic"], 50257, 5, 30)
    b = traffic.open_loop_schedule(burst["traffic"], 50257, 5, 30)
    assert len(a) == len(b) == 24
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))
    due = np.array([r.due for r in b])
    # nothing is due inside a pause (the last is due as its burst closes)
    bins = np.histogram(due, bins=[0, 5, 10, 15, 20, 25 + 1e-6, 30])[0]
    assert bins[1] == bins[3] == bins[5] == 0 and bins[::2].sum() == 24
    assert 5 <= bins[::2].min() and bins[::2].max() <= 11
