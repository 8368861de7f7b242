"""The benchmark's arithmetic for the cell whose latent cache is of two layer
kinds (``benchmark/lib/mla_window_cost.py``), its three readers on a cut trace
and on a program that lacks what they read, the ``serve_latent_window``
runner's shapes, check lengths and key counts, the configuration's file
against the catalog row, and the rehearsal end to end on the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import (harness, mla_cost, mla_window_cost, peaks,
                           program_trace, xplane)
from benchmark.runners import serve_latent_window as runner

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
V5E = peaks.peaks_for("TPU v5 lite")
CONFIG = "dots3-note-prev-d5e16"
CELL = CONFIG + ".transcript-notes-peak"
WIN = {"heads": 64, "row_width": 1152, "latent_dim": 1088, "value_dim": 1024,
       "itemsize": 2, "window": 513, "layers": 3}
READERS = ["serve.mla_window_attention_share_pct",
           "serve.mla_window_attention_roofline_pct",
           "serve.mla_window_walk_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _reader(name):
    return harness.read_layer_metric(name)


def test_a_decode_row_is_on_the_bytes_side_and_a_chunk_on_the_operations():
    """A decode row reads its window's 513 rows of 2304 bytes once: 117
    operations a byte against the chip's 240. A chunk's queries share their
    windows' rows and are floored by operations alone: 0.29 TFLOP a layer for
    2048 queries of 513 keys — half of what a walk of two 512-row blocks
    computes."""
    keys = 32 * 513 * 3
    cost = mla_window_cost.decode_cost(keys, 32 * 3, WIN)
    assert cost["bytes"] == keys * 2304 + 32 * 3 * 64 * 2176 * 2
    assert cost["flops"] == 2 * 64 * keys * 2112
    assert cost["flops"] / (keys * 2304) == pytest.approx(117.3, rel=1e-3)
    assert mla_cost.floor_seconds(cost, V5E)["bound"] == "bytes"
    chunk = mla_window_cost.prefill_cost(2048 * 513, WIN)
    assert chunk == {"bytes": 0, "flops": 2 * 64 * 2048 * 513 * 2112}
    assert chunk["flops"] == pytest.approx(0.284e12, rel=1e-2)
    assert mla_cost.floor_seconds(chunk, V5E)["bound"] == "operations"
    shape = dict(WIN, traced={"rows_decode": 32 * 3, "keys_decode": keys,
                              "keys_prefill": 3 * 2048 * 513})
    assert mla_window_cost.traced_floor_seconds(shape, V5E) == pytest.approx(
        cost["bytes"] / 819e9 + 3 * chunk["flops"] / 197e12, rel=1e-6)


def test_the_kernel_readers_on_a_cut_trace(monkeypatch):
    """Two window-attention calls, a dense latent call (cell 6's kernel: not
    these readers') and a fusion inside ``bench.window``: the share is the
    calls' self time over busy, the roofline the window's floor over their
    time."""
    K = ('%{}.{} = bf16[4,8]{{1,0}} custom-call(bf16[4,8]{{1,0}} %p.1), '
         'custom_call_target="tpu_custom_call"')
    F = "%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p.2), kind=kLoop"
    pt = program_trace.ProgramTrace({
        xplane.HOST_PLANE: {"python3": [("bench.window", 0, 1_000_000)]},
        "/device:TPU:0": {xplane.OPS_LINE: [
            (K.format("pt_mla_window_attention", 3), 0, 300_000),
            (F, 300_000, 500_000),
            (K.format("pt_mla_window_attention", 4), 500_000, 600_000),
            (K.format("pt_mla_paged_attention", 6), 600_000, 800_000)]}})
    monkeypatch.setattr(program_trace, "current", lambda shapes, kind: pt)
    import jax

    monkeypatch.setattr(jax.devices()[0].__class__, "device_kind",
                        "TPU v5 lite", raising=False)
    shapes = {"kind": "serve", "mla_window": dict(WIN, traced={
        "rows_decode": 0, "keys_decode": 0, "keys_prefill": 2048 * 513})}
    assert _reader(READERS[0]).reduce(None, {}, {}, shapes) == \
        pytest.approx(100 * 400 / 800)
    floor = 2 * 64 * 2048 * 513 * 2112 / 197e12
    assert _reader(READERS[1]).reduce(None, {}, {}, shapes) == \
        pytest.approx(100 * floor / 400e-6, rel=1e-6)


def test_the_walk_reader_reads_two_counters():
    counters = {"attn_rows_walked_window_total": 3 * 1024 * 256,
                "attn_rows_in_window_total": 3 * 520 * 256}
    assert _reader(READERS[2]).reduce(None, counters, {}, {}) == \
        pytest.approx(100 * 1024 / 520)
    # walked what the windows hold: nothing masked was read
    assert _reader(READERS[2]).reduce(
        None, {"attn_rows_walked_window_total": 7,
               "attn_rows_in_window_total": 7}, {}, {}) == 100.0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_program_that_lacks_it(name):
    """The parent has no such kernel or counter: the reader returns ``None``
    and does not raise (a train cell's shapes, a serve cell of another model,
    an untraced run of this one)."""
    mod = _reader(name)
    for shapes in ({"kind": "train"}, {"kind": "serve", "chips": 1}, {},
                   {"kind": "serve", "mla_window": dict(WIN, traced=None)}):
        assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_new_entries_stand_behind_what_was_there():
    bench = _load("..", "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index("brumby-14b-d8.doc-reasoning-peak")
    cell = bench["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "transcript-notes-peak", 1)
    assert len(cell["why"]) <= 200 and "13 of 46" in cell["why"]
    assert cell["why"] == _load("workloads", CELL + ".json")["why"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert CELL in by[name]["workloads"], name
        assert by[name]["moves"] == "serve_tokens_per_s"
        assert by[name]["layer"] == by["serve.mla_attention_share_pct"][
            "layer"]
        assert ("roofline" in name) == (by[name]["better"] == "higher")
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    # behind what was there, and no pin on being the last: the next cell goes
    # behind this one
    assert tokens["workloads"].index(CELL) > tokens["workloads"].index(
        "brumby-14b-d8.doc-reasoning-peak")
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"serve.window_keys_pct", "serve.moe_held_pairs_pct",
            "serve.prefill_chunk_p50_ms", "serve.moe_experts_roofline_pct",
            "serve.run_ahead_pct", "serve.part_attention_share_pct",
            "serve.part_unscoped_share_pct"} <= mine


def test_the_configuration_holds_every_catalog_key_and_states_its_cut():
    cfg = _load("configs", CONFIG + ".json")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots3-note-prev")
        assert cfg["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert sorted(differs) == sorted(cfg["reduced"])
    assert list(cfg["reduced"]) == ["num_hidden_layers", "n_routed_experts"]
    assert (cfg["reduced"]["num_hidden_layers"]["published"],
            cfg["reduced"]["num_hidden_layers"]["here"]) == (46, 5)
    assert (cfg["reduced"]["n_routed_experts"]["published"],
            cfg["reduced"]["n_routed_experts"]["here"]) == (256, 16)
    assert (cfg["router_experts"], cfg["held_experts_first"]) == (256, 0)
    # the widths, as published
    assert (cfg["hidden_size"], cfg["kv_lora_rank"], cfg["swa_kv_lora_rank"],
            cfg["num_attention_heads"], cfg["swa_num_attention_heads"],
            cfg["index_n_heads"], cfg["index_topk"],
            cfg["sliding_window_size"], cfg["vocab_size"]) == (
        5120, 512, 1024, 128, 64, 64, 2048, 513, 152064)
    assert len(cfg["layer_types"]) == 46
    assert cfg["layer_types"][:5] == ["full_attention"] * 2 + \
        ["sliding_attention"] * 3
    said = " ".join(cfg["assumed"]) + cfg["deployment"]
    for word in ("LongCat-Flash", "vision tower", "multi-token-prediction",
                 "pre-norm", "INTERLEAVED", "counts the query's own"):
        assert word in said, word
    e = cfg["system"]["engine"]
    assert (e["max_slots"], e["max_seq_len"], e["page_len"]) == (32, 67584,
                                                                 128)
    assert e["prefill_buckets"] == [256, 512, 2048] and not e["prefix_cache"]
    # the parameters of the cut, in this repo's bytes
    full = 5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 \
        + 16384 * 5120 + 5120 * 128
    indexer = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
    window = 5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320 \
        + 8192 * 5120 + 5120 * 64
    expert, router = 3 * 5120 * 1536, 5120 * 256
    moe = 17 * expert + router
    total = (full + indexer + 3 * 5120 * 13824) + (full + indexer + moe) \
        + 3 * (window + moe) + 2 * 152064 * 5120
    assert total / 1e6 == pytest.approx(3939.6, abs=0.5)
    # the model the configuration builds is that model
    from benchmark.runners.serve_recurrent import model_config

    built = model_config(cfg)
    assert built.layer_kinds() == ["full", "full"] + ["window"] * 3
    assert [built.is_dense(i) for i in range(5)] == [True] + [False] * 4
    spec = built.served_model().cache_spec
    assert (spec["dim"], spec["window_row"]["dim"], spec["window"]) == (
        576, 1088, 513)
    # the pools: what the engine is given fits what it asks for
    from paddle_tpu.serving.paged_kv import window_page_bound

    need = e["max_slots"] * window_page_bound(513, 1, 128) \
        + window_page_bound(513, 2048, 128) + 1
    assert e["window_pages"] >= need
    full_gb = e["num_pages"] * 128 * 2 * (640 + 128) * 2 / 1e9
    assert 4.0 <= full_gb <= 5.0


def test_the_traffic_is_the_issues():
    w = _load("workloads", CELL + ".json")
    tr = w["traffic"]
    assert w["kind"] == "serve_latent_window" and w["chips"] == 1
    assert (tr["prompt_len"]["median"], tr["prompt_len"]["sigma"],
            tr["prompt_len"]["min"], tr["prompt_len"]["max"]) == (
        6144, 0.9, 1024, 65536)  # the issue's fallback median: see the knee
    assert (tr["output_len"]["median"], tr["output_len"]["sigma"],
            tr["output_len"]["min"], tr["output_len"]["max"]) == (
        384, 0.7, 64, 1536)
    assert tr["order_seed"] == 0 and tr["bursts"] is None
    assert tr["shared_prefix"]["share"] == 0.0
    assert tr["prompt_len"]["max"] + tr["output_len"]["max"] <= 67584
    assert w["knee"]["requests_per_s"] and w["knee"]["table"]


def test_the_check_places_a_long_and_a_short_prompt_and_counts_keys():
    tr = _load("workloads", CELL + ".json")["traffic"]
    for seed in (1, 5000000077):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 99]))
        lens = runner._check_lengths(32, tr, 8, 32768, 2048, rng)
        assert len(lens) == 32
        checked = [p for p, _o in lens[::8]]
        assert checked[0] > 32768 and checked[1] < 2048
        assert sorted(p for p, _o in lens)[0] >= 1024
        # the first is the SHORTEST past the mark, the second the LONGEST below
        assert checked[0] == min(p for p, _o in lens if p > 32768)
        assert checked[1] == max(p for p, _o in lens if p < 2048)
    assert runner.keys_selected([3, 600], 513, 3) == 3 * (
        6 + 513 * 514 // 2 + 87 * 513)
    assert runner.keys_selected([3000], 2048, 2) == 2 * (
        2048 * 2049 // 2 + 952 * 2048)


def test_the_runners_shapes_carry_what_the_traced_calls_covered():
    spec = harness.Spec(CELL)
    bare = runner._kernel_shapes(spec, {})
    assert bare["mla_window"]["traced"] is None
    assert {k: bare["mla_window"][k] for k in WIN} == WIN
    assert bare["ranged"]["layers"] == {"full": {"count": 2},
                                        "window": {"count": 3}}
    assert (bare["dsa"]["heads"], bare["dsa"]["index_heads"],
            bare["dsa"]["layers"], bare["dsa"]["full_layers"]) == (128, 64, 2,
                                                                   2)
    traced = dict.fromkeys(runner._WINDOW_COUNTERS, 0)
    traced.update(slot_rounds=40, attn_keys_window_decode_total=60000,
                  attn_keys_window_prefill_total=3000000,
                  attn_keys_window_total=3060000)
    got = runner._kernel_shapes(spec, traced)["mla_window"]["traced"]
    assert got == {"rows_decode": 120, "keys_decode": 60000,
                   "keys_prefill": 3000000}
    # what ``serve.window_keys_pct`` (cell 8's reader) makes of the same names
    assert _reader("serve.window_keys_pct").reduce(
        None, {"attn_keys_full_total": 2 * 10000,
               "attn_keys_window_total": 3 * 2000}, {}, bare) == \
        pytest.approx(20.0)


def test_the_benchmarks_reference_is_the_models():
    with open(os.path.join(BENCH, "lib", "reference_dots3_note.py")) as f, \
            open(os.path.join(REPO, "paddle_tpu", "models", "reference",
                              "dots3_note.py")) as g:
        assert f.read() == g.read()


def test_the_rehearsal_reports_the_new_counters_reader():
    """The cell end to end on the CPU at the rehearsal's sizes, traced: the
    check passes through both caches and the walk reader (program counters:
    no device needed) is in the line; the two device readers find no device
    plane and are left out."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5000000021", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["rehearsal"] is True and line["failed"] == 0
    assert line["metrics"]["serve.mla_window_walk_pct"]["value"] >= 100.0
    assert 0 < line["metrics"]["serve.window_keys_pct"]["value"] < 100.0
    assert READERS[0] not in line["metrics"]
    notes = line["notes"]
    assert notes["window_pages_released"] > 0
    assert notes["selection_shared"] == 1.0
    assert {"carried_rounds_pct", "page_write_pct",
            "index_selected_pct"} <= set(notes)
