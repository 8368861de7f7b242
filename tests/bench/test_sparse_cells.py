"""The benchmark's arithmetic for the cell whose latent cache holds an index
row (``benchmark/lib/dsa_cost.py``), its six readers on a cut trace and on a
program that lacks what they read, the ``serve_sparse`` runner's shapes, check
lengths and key count, and the configuration's file against the published
keys."""
import json
import os

import numpy as np
import pytest

from benchmark.lib import (dsa_cost, harness, mla_cost, part_time, peaks,
                           program_trace, traffic, xplane)
from benchmark.runners import serve_sparse

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
V5E = peaks.peaks_for("TPU v5 lite")
CELL = "glm-5.2-d5e16.repo-qa-peak"
DSA = {"heads": 64, "row_width": 640, "latent_dim": 576, "value_dim": 512,
       "index_heads": 32, "index_dim": 128, "topk": 2048, "itemsize": 2,
       "layers": 5, "full_layers": 2}
READERS = ["serve.indexer_share_pct", "serve.sparse_attention_share_pct",
           "serve.sparse_attention_roofline_pct",
           "serve.index_scores_share_pct", "serve.index_scores_roofline_pct",
           "serve.index_selected_pct"]


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _reader(name):
    return harness.read_layer_metric(name)


def test_a_decode_row_is_on_the_bytes_side_of_both_floors():
    """A decode row reads the 2048 rows it selected (1280 bytes each as the
    arena lays them out) and its own index keys: 109 and 32 operations a
    byte against the chip's 240."""
    cost = dsa_cost.attend_decode_cost(32 * 2048 * 5, 32, DSA)
    assert cost["bytes"] == 32 * 2048 * 5 * 1280 + 32 * 5 * 64 * 1152 * 2
    assert cost["flops"] == 2 * 64 * 32 * 2048 * 5 * 1088
    floor = mla_cost.floor_seconds(cost, V5E)
    assert floor["bound"] == "bytes"
    assert floor["seconds"] == pytest.approx(0.5409e-3, rel=1e-3)
    index = dsa_cost.index_decode_cost(32 * 15000 * 2, DSA)
    assert index["bytes"] == 32 * 15000 * 2 * 256
    assert index["flops"] == 8192 * 32 * 15000 * 2
    assert mla_cost.floor_seconds(index, V5E)["bound"] == "bytes"


def test_a_chunk_is_on_the_operations_side_and_reads_no_bytes():
    """A chunk's neighbouring queries may share a selected row, so a chunk
    is floored by its operations alone — 0.58 TFLOP a layer for 2048 queries
    of 2048 keys, 2.8 ms of index scores at 32 k."""
    cost = dsa_cost.attend_prefill_cost(2048 * 2048, DSA)
    assert cost == {"bytes": 0, "flops": 2 * 64 * 2048 * 2048 * 1088}
    assert cost["flops"] == pytest.approx(0.584e12, rel=1e-3)
    assert mla_cost.floor_seconds(cost, V5E)["bound"] == "operations"
    keys = 2048 * 32768
    assert mla_cost.floor_seconds(dsa_cost.index_prefill_cost(keys, DSA),
                                  V5E)["seconds"] == \
        pytest.approx(2.79e-3, rel=1e-2)
    shape = dict(DSA, traced={
        "rows_decode": 32, "selected_decode": 32 * 2048 * 5,
        "selected_prefill": 2048 * 2048 * 5, "scored_decode": 960000,
        "scored_prefill": 2 * keys})
    assert dsa_cost.attend_floor_seconds(shape, V5E) == pytest.approx(
        0.5409e-3 + 5 * 0.584e12 / 197e12, rel=1e-3)
    assert dsa_cost.index_floor_seconds(shape, V5E) == pytest.approx(
        960000 * 256 / 819e9 + 2 * 2.79e-3, rel=1e-2)


def test_the_kernel_readers_on_a_cut_trace(monkeypatch):
    """Two selected-attention calls, an index-scores call, a dense latent
    call (cell 6's kernel: not these readers') and a fusion inside
    ``bench.window``: a share is the calls' self time over busy, a roofline
    the floor over their time."""
    K = ('%{}.{} = bf16[4,8]{{1,0}} custom-call(bf16[4,8]{{1,0}} %p.1), '
         'custom_call_target="tpu_custom_call"')
    F = "%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p.2), kind=kLoop"
    pt = program_trace.ProgramTrace({
        xplane.HOST_PLANE: {"python3": [("bench.window", 0, 1_000_000)]},
        "/device:TPU:0": {xplane.OPS_LINE: [
            (K.format("pt_dsa_index_scores", 3), 0, 100_000),
            (K.format("pt_mla_sparse_attention", 4), 100_000, 500_000),
            (F, 500_000, 600_000),
            (K.format("pt_mla_sparse_attention", 5), 600_000, 800_000),
            (K.format("pt_mla_paged_attention", 6), 800_000, 900_000)]}})
    monkeypatch.setattr(program_trace, "current", lambda shapes, kind: pt)
    import jax

    monkeypatch.setattr(jax.devices()[0].__class__, "device_kind",
                        "TPU v5 lite", raising=False)
    shapes = {"kind": "serve", "dsa": dict(DSA, traced={
        "rows_decode": 32, "keys_decode": 0, "keys_prefill": 0,
        "selected_decode": 32 * 2048 * 5, "selected_prefill": 0,
        "scored_decode": 0, "scored_prefill": 2048 * 4096})}
    assert _reader("serve.sparse_attention_share_pct").reduce(
        None, {}, {}, shapes) == pytest.approx(100 * 600 / 900)
    assert _reader("serve.index_scores_share_pct").reduce(
        None, {}, {}, shapes) == pytest.approx(100 * 100 / 900)
    assert _reader("serve.sparse_attention_roofline_pct").reduce(
        None, {}, {}, shapes) == pytest.approx(100 * 0.5409e-3 / 600e-6,
                                               rel=1e-3)
    floor = 8192 * 2048 * 4096 / 197e12
    assert _reader("serve.index_scores_roofline_pct").reduce(
        None, {}, {}, shapes) == pytest.approx(100 * floor / 100e-6, rel=1e-3)


def test_the_indexer_reader_counts_the_ops_under_its_scope_alone():
    """Ops whose own name stack holds ``pt.indexer`` (whatever part is around
    it, whatever is nested inside), by self time over busy; an unnamed op
    beside them is NOT theirs, and a trace without the scope reads nothing."""
    reader = _reader("serve.indexer_share_pct")
    stacks = ["jit(pt_window1)/pt.attn_proj/pt.indexer/dot_general:",
              "jit(pt_window1)/pt.attention/pt.indexer/jit(index_select)/"
              "pt.norm/reduce:", "jit(pt_window1)/pt.attention/custom-call:",
              "", "jit(pt_window1)/pt.mlp/dot_general:"]
    own = [part_time.part_of(s, ("indexer",)) for s in stacks]
    assert own == ["indexer", "indexer", None, None, None]
    ops = [(own[0], 0.0, 100.0), (own[1], 100.0, 250.0),
           (own[2], 250.0, 650.0), (own[3], 650.0, 700.0),
           (own[4], 800.0, 1000.0)]
    runs = [(0.0, 1000.0)]
    assert reader.share_pct([(ops, runs)], "indexer", 0.0, 1000.0) == \
        pytest.approx(100 * 250 / 900)
    assert reader.share_pct([(ops, runs)], "indexer", 50.0, 1000.0) == \
        pytest.approx(100 * 200 / 850)         # clipped to the window
    assert reader.share_pct([(ops[2:], runs)], "indexer", 0, 1000) is None


def test_the_selected_share_reads_the_counters():
    counters = {"attn_keys_decode_total": 1000, "attn_keys_prefill_total":
                9000, "attn_keys_selected_decode_total": 2500,
                "attn_keys_selected_prefill_total": 10000}
    assert _reader("serve.index_selected_pct").reduce(
        None, counters, {}, {"kind": "serve", "dsa": DSA}) == \
        pytest.approx(100 * 12500 / 50000)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_program_that_lacks_it(name):
    """The parent has no such kernel, scope or counter: the reader returns
    ``None`` and does not raise (a train cell's shapes, a serve cell of
    another model, an untraced run of this one)."""
    mod = _reader(name)
    for shapes in ({"kind": "train"}, {"kind": "serve", "chips": 1}, {},
                   {"kind": "serve", "dsa": dict(DSA, traced=None)}):
        assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_every_new_reader_is_listed_for_this_cell_alone():
    bench = _load("..", "BENCHMARK.json")
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "glm-5.2-d5e16"
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert by[name]["workloads"] == [CELL], name
        assert by[name]["moves"] == "serve_tokens_per_s"
        assert ("roofline" in name) == (by[name]["better"] == "higher")
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
    # a program_span reader built on program_trace is among the cell's
    assert CELL in by["serve.run_ahead_pct"]["workloads"]
    for name in ("serve.carried_rounds_pct", "serve.page_write_pct",
                 "serve.part_router_share_pct", "serve.part_experts_share_pct",
                 "serve.mla_attention_share_pct"):
        assert CELL not in by[name]["workloads"], name


def test_the_runners_shapes_carry_what_the_traced_calls_covered():
    spec = harness.Spec(CELL)
    assert serve_sparse._kernel_shapes(spec, {})["dsa"]["traced"] is None
    traced = dict.fromkeys(serve_sparse._WINDOW_COUNTERS, 0)
    traced.update(attn_keys_decode_total=1000, attn_keys_prefill_total=5000,
                  index_keys_scored_decode_total=2000,
                  index_keys_scored_prefill_total=10000,
                  attn_keys_selected_decode_total=700,
                  attn_keys_selected_prefill_total=3000, slot_rounds=40,
                  moe_held_pairs_total=64, moe_experts_hit_total=48)
    got = serve_sparse._kernel_shapes(spec, traced)
    assert {k: v for k, v in got["dsa"].items() if k != "traced"} == DSA
    assert got["dsa"]["traced"] == {
        "rows_decode": 40, "keys_decode": 1000, "keys_prefill": 5000,
        "scored_decode": 2000, "scored_prefill": 10000,
        "selected_decode": 700, "selected_prefill": 3000}
    assert got["moe"] == {"hidden": 6144, "width": 2048, "itemsize": 2,
                          "traced": {"rows": 64, "experts_hit": 48}}


def test_one_checked_prompt_is_past_32768_and_the_others_are_typical():
    wl = _load("workloads", CELL + ".json")
    tr, every, n = wl["traffic"], wl["check_every"], 32
    assert wl["long_prompt"] == 32768 and tr["prompt_len"]["min"] == 4096
    for seed in (0, 7, 4400000077):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 99]))
        lens = serve_sparse._check_lengths(n, tr, every, 32768, rng)
        checked = [p for p, _o in lens[::every]]
        assert len(checked) == 4 and checked[0] > 32768
        past = sorted(p for p, _o in lens if p > 32768)
        assert checked[0] == past[0]           # the shortest of them
        assert sorted(p for p, _o in lens) == sorted(
            traffic.lognormal_quantiles(n, tr["prompt_len"]).tolist())
        assert max(p + o for p, o in lens) <= 49664


def test_the_keys_attended_are_counted_from_the_lengths():
    """``sum_t min(t + 1, topk)`` over a sequence's consumed positions, a
    layer: all of them up to ``topk``, ``topk`` each beyond."""
    assert serve_sparse.keys_selected([5], 2048, 5) == 5 * 15
    assert serve_sparse.keys_selected([2048], 2048, 1) == 2048 * 2049 // 2
    assert serve_sparse.keys_selected([5000, 3], 2048, 2) == 2 * (
        2048 * 2049 // 2 + (5000 - 2048) * 2048 + 6)
    brute = sum(min(t + 1, 6) for n in (40, 7) for t in range(n))
    assert serve_sparse.keys_selected([40, 7], 6, 3) == 3 * brute


def test_the_configuration_holds_every_published_key_unchanged():
    cfg = _load("configs", "glm-5.2-d5e16.json")
    reduced = {"num_hidden_layers": (78, 5), "first_k_dense_replace": (3, 1),
               "n_routed_experts": (256, 16),
               "num_nextn_predict_layers": (1, 0)}
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GLM-5.2")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            want = reduced[key][1] if key in reduced else value
            assert cfg[key] == want, key
    assert list(cfg["reduced"]) == list(reduced)
    for key, (published, here) in reduced.items():
        entry = cfg["reduced"][key]
        assert (entry["published"], entry["here"]) == (published, here)
        assert cfg[key] == here and entry["why"]
        assert not key.endswith(("_size", "_dim", "_rank"))
    # the three keys this file adds to the published ones
    assert (cfg["layer_offset"], cfg["router_experts"],
            cfg["held_experts_first"]) == (2, 256, 0)
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["index_n_heads"], cfg["index_head_dim"],
            cfg["index_topk"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["vocab_size"]) == (6144, 2048, 512, 192, 64, 256, 32, 128,
                                   2048, 2048, 12288, 8, 154880)
    assert len(cfg["indexer_types"]) == len(cfg["mlp_layer_types"]) == 78
    assert cfg["indexer_types"][2:7] == ["full", "shared", "shared",
                                         "shared", "full"]
    assert cfg["mlp_layer_types"][2:7] == ["dense"] + ["sparse"] * 4
    assert len(cfg["assumed"]) >= 8 and "pipeline stages" in cfg["deployment"]
    e = cfg["system"]["engine"]
    assert (e["max_slots"], e["max_seq_len"], e["page_len"],
            e["prefill_buckets"][-1]) == (32, 49664, 128, 2048)
    # the rehearsal selects: topk below its contexts
    r = cfg["rehearsal"]
    assert r["index_topk"] < _load("workloads", CELL + ".json")[
        "rehearsal"]["traffic"]["prompt_len"]["min"]


def test_the_model_reads_the_file_as_its_config_class():
    from benchmark.runners.serve_recurrent import model_config

    cfg = model_config(harness.Spec(CELL).config)
    assert cfg.layer_kinds() == ["full", "shared", "shared", "shared", "full"]
    assert [cfg.is_dense(i) for i in range(5)] == [True] + [False] * 4
    spec = cfg.served_model().cache_spec
    assert spec["dim"] == 576 and spec["index"]["dim"] == 128
    shapes = cfg.served_model().param_shapes()
    n = sum(int(np.prod(a.shape)) for layer in shapes["layers"]
            for a in layer.values()) + 2 * 154880 * 6144 + 6144
    assert n == pytest.approx(5.547e9, rel=2e-3)
