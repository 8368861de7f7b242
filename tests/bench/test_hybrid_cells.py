"""The benchmark's arithmetic for the cell whose model keeps memory by layer
kind (``benchmark/lib/moe_relu2_cost.py``, and ``ssm_cost.py`` at its shape)
against a hand count, its two readers on made-up traces and on a program that
lacks what they read, the ``serve_hybrid`` runner's shapes, the
configuration's file against the catalog row, the traffic, and the rehearsal
end to end on the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import (harness, kernel_time, kv_attention_cost, mhc_cost,
                           moe_cost, moe_relu2_cost, part_time, peaks,
                           ssm_cost, traffic)
from benchmark.runners import serve_hybrid as runner

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
V5E = peaks.peaks_for("TPU v5 lite")
CONFIG = "nemotron-3-nano-30b-a3b-d9"
CELL = CONFIG + ".agent-reasoning-peak"
RELU2 = {"hidden": 2688, "width": 1856, "itemsize": 2}
READERS = ["serve.relu2_experts_roofline_pct", "serve.ssm_scan_share_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _reader(name):
    return harness.read_layer_metric(name)


def test_an_ungated_expert_streams_two_matrices():
    """One expert, one row: two matrices of 2688 x 1856 bfloat16; the row in
    at hidden, float32 then bfloat16 at width between the two, out at
    hidden. Read by the three-matrix arithmetic the same counts give a floor
    1.5 x too high."""
    cost = moe_relu2_cost.gmm_cost(1, 1, RELU2)
    assert cost["bytes"] == 2 * 2688 * 1856 * 2 + \
        (2688 * 2 + 1856 * 4 + 1856 * 2 + 2688 * 2)
    assert cost["flops"] == 2 * 2 * 2688 * 1856
    gated = moe_cost.gmm_cost(1, 1, RELU2)
    assert gated["flops"] == 1.5 * cost["flops"]
    assert 1.49 < gated["bytes"] / cost["bytes"] < 1.51
    # a 128-row round of one layer: 768 pairs, every one of 128 experts hit
    # -> 2.555 GB of weights, 3.12 ms at 819 GB/s, bound by bytes
    rnd = moe_relu2_cost.floor_seconds(
        moe_relu2_cost.gmm_cost(768, 128, RELU2), V5E)
    assert rnd["bound"] == "bytes"
    assert rnd["seconds"] == pytest.approx(3.134e-3, rel=2e-3)
    # a 2048-token chunk: 12288 pairs a layer, 96 a expert: still the bytes'
    # (an expert needs 241 rows before its operations outweigh its weights)
    chunk = moe_relu2_cost.floor_seconds(
        moe_relu2_cost.gmm_cost(12288, 128, RELU2), V5E)
    assert chunk["bound"] == "bytes"
    many = moe_relu2_cost.floor_seconds(
        moe_relu2_cost.gmm_cost(128 * 400, 128, RELU2), V5E)
    assert many["bound"] == "operations"


def test_a_step_of_this_state_is_a_gigabyte_a_layer():
    """``ssm_cost`` is generic in rows, heads, head width, state and groups:
    at [128, 64, 64, 128] with 8 groups the state is 268 MB a layer, read and
    written: 0.66 ms at 819 GB/s."""
    shape = runner._kernel_shapes(harness.Spec(CELL), {})["ssm_step"]
    assert shape == {"rows": 128, "heads": 64, "d_head": 64, "d_state": 128,
                     "groups": 8}
    args = tuple(shape.values())
    assert ssm_cost.ssm_step_bytes(*args) == 2 * 128 * 64 * 64 * 128 * 4 + \
        4 * (2 * 128 * 64 * 64 + 2 * 128 * 8 * 128 + 2 * 128 * 64)
    floor = ssm_cost.floor_seconds(shape, V5E)
    assert floor["bound"] == "bytes"
    assert floor["seconds"] == pytest.approx(0.661e-3, rel=5e-3)


def test_the_roofline_reader_divides_the_two_matrix_floor(monkeypatch):
    import jax

    monkeypatch.setattr(jax.devices()[0].__class__, "device_kind",
                        "TPU v5 lite", raising=False)
    monkeypatch.setattr(kernel_time, "seconds_in_window",
                        lambda pt, prefix: 5e-3 if prefix == "gmm" else None)
    shapes = {"kind": "serve", "moe_relu2": dict(
        RELU2, traced={"rows": 768, "experts_hit": 128})}
    got = _reader(READERS[0]).reduce(None, {}, {}, shapes)
    assert got == pytest.approx(100 * 3.134e-3 / 5e-3, rel=2e-3)
    assert got < 100
    # the cell hands NO ``moe`` shape: the three-matrix reader finds nothing
    assert _reader("serve.moe_experts_roofline_pct").reduce(
        None, {}, {}, shapes) is None
    idle = {"kind": "serve", "moe_relu2": dict(RELU2, traced=None)}
    assert _reader(READERS[0]).reduce(None, {}, {}, idle) is None


def test_the_scans_scope_is_read_kernel_or_not(monkeypatch):
    """Ops whose own name stack holds ``pt.ssm_scan`` — the step's Mosaic
    call and the chunked scan's XLA ops alike — by self time over busy time;
    the projections around them in the same ``mixer`` are not theirs."""
    stacks = ["jit(pt_window1)/pt.mixer/pt.ssm_scan/pt_ssm_step:",
              "jit(pt_prefill2048)/pt.mixer/pt.ssm_scan/dot_general:",
              "jit(pt_prefill2048)/pt.mixer/dot_general:", "",
              "jit(pt_window1)/pt.mlp/pt.experts/gmm:"]
    from paddle_tpu.observability.trace.parts import SUBPARTS

    own = [part_time.part_of(s, SUBPARTS) for s in stacks]
    assert own == ["ssm_scan", "ssm_scan", None, None, None]
    ops = [(own[0], 0.0, 100.0), (own[1], 100.0, 250.0),
           (own[2], 250.0, 650.0), (own[3], 650.0, 700.0),
           (own[4], 800.0, 1000.0)]
    assert mhc_cost.scope_ns([(ops, [(0.0, 1000.0)])], "ssm_scan", 0.0,
                             1000.0) == (250.0, 900.0)
    monkeypatch.setattr(mhc_cost, "traced_scope_ns",
                        lambda shapes, name: (9e6, 60e6)
                        if name == "ssm_scan" else None)
    assert _reader(READERS[1]).reduce(None, {}, {}, {"kind": "serve"}) == 15.0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_program_that_lacks_it(name):
    """The parent has no such scope, shape or counter: the reader returns
    ``None`` and does not raise (a train cell's shapes, a serve cell of
    another model, an untraced run of this one)."""
    mod = _reader(name)
    for shapes in ({"kind": "train"}, {"kind": "serve", "chips": 1}, {},
                   {"kind": "serve", "moe_relu2": dict(RELU2, traced=None)},
                   {"kind": "serve", "moe": dict(RELU2, traced={
                       "rows": 5, "experts_hit": 2})}):
        assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_new_entries_stand_behind_what_was_there():
    bench = _load("..", "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index(
        "xing4.0-29b-a4b-d5.rag-extract-peak")
    cell = bench["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "agent-reasoning-peak", 1)
    assert len(cell["why"]) <= 200
    assert cell["why"] == _load("workloads", CELL + ".json")["why"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers",
                                "hybrid_override_pattern"]
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   for k in entry["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    by = {m["name"]: m for m in bench["per_layer"]}
    # the two new readers are no entries: ``test_train_parts.py`` pins the
    # list's end and the middle is not a PR's to write to; the runner reads
    # them into a traced run's notes
    assert not set(READERS) & set(by)
    assert tuple(READERS) == runner.NOTED_READERS
    for name in READERS:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"].index(CELL) > tokens["workloads"].index(
        "xing4.0-29b-a4b-d5.rag-extract-peak")
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"serve.ssm_step_share_pct", "serve.ssm_step_roofline_pct",
            "serve.state_install_p50_ms",
            "serve.moe_experts_share_pct", "serve.ranged_attention_share_pct",
            "serve.ranged_attention_roofline_pct",
            "serve.prefill_chunk_p50_ms", "serve.run_ahead_pct",
            "serve.occupancy_pct", "serve.part_attention_share_pct",
            "serve.part_mlp_share_pct", "serve.part_unscoped_share_pct"} \
        <= mine
    # three-matrix arithmetic: not this cell's; and the lists tests pin to
    # other cells stay theirs
    for name in ("serve.moe_experts_roofline_pct",
                 "serve.state_resumed_chunks_pct",
                 "serve.part_router_share_pct",
                 "serve.part_experts_share_pct",
                 "serve.part_mixer_share_pct", "serve.carried_rounds_pct"):
        assert CELL not in by[name]["workloads"], name
    assert len(names) == len(set(names)) and \
        sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_configuration_holds_every_catalog_key_and_states_its_cut():
    """Every key of the catalog row's ``config`` is in the file unchanged but
    the two under ``reduced``, each with published / here / why; no width, no
    expert, no head and no vocabulary row is among them."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not installed here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    cfg = _load("configs", CONFIG + ".json")
    entry = next(c for c in _load("..", "BENCHMARK.json")["configs"]
                 if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == row["source_url"]
    reduced = cfg["reduced"]
    assert list(reduced) == entry["reduced"]
    for key, want in row["config"].items():
        if key in reduced:
            assert reduced[key]["published"] == want
            assert reduced[key]["here"] == cfg[key] != want
            assert reduced[key]["why"]
        else:
            assert cfg[key] == want, key
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"]) == (
        9, "MEMEM*EME")
    assert row["config"]["hybrid_override_pattern"].startswith("MEMEM*EME")
    assert (cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["moe_intermediate_size"], cfg["mamba_num_heads"],
            cfg["num_key_value_heads"]) == (128, 131072, 1856, 64, 2)
    assert cfg["parameters"]["here_GB_bfloat16"] == 12.15
    assert "first of 6 pipeline stages" in cfg["deployment"]
    assert any("NO rotary embedding" in a for a in cfg["assumed"])
    assert len(cfg["assumed"]) >= 8
    e = cfg["system"]["engine"]
    assert (e["max_slots"], e["max_seq_len"], e["page_len"],
            e["prefill_buckets"][-1], e["prefix_cache"]) == (
        128, 8192, 128, 2048, False)
    assert (e["num_pages"] - 1) * e["page_len"] == 524288   # 0.5 M tokens
    # the model's config class takes the file's keys letter for letter
    from benchmark.runners.serve_recurrent import model_config

    mc = model_config(cfg)
    assert (mc.mamba_inner, mc.conv_dim, mc.in_proj_dim, mc.expert_lanes,
            mc.dtype) == (4096, 6144, 10304, 1920, "bfloat16")
    sm = mc.served_model()
    assert sm.cache_spec["layers"] == [
        "state", "none", "state", "none", "state", "full", "none", "state",
        "none"]
    import jax

    n = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in
            jax.tree_util.tree_leaves(sm.param_shapes()))
    # as stored: + 4 x 128 x 2 x 2688 x 64 lanes of padding, + the float32
    # router and state-space parameters at 4 B
    assert abs(n / 1e9 - (12.15 + 0.352)) < 0.01
    # weights and caches fill at least 80 % of the chip
    per_slot = sum(int(np.prod(s)) * 4 for s, _dt in sm.state_spec.values())
    state = e["max_slots"] * 4 * per_slot
    pool = 2 * e["num_pages"] * 2 * 128 * 128 * 2
    assert (state, pool) == (1111490560, 537001984)
    assert (n + state + pool) / 16_909_336_064 > 0.80


def test_the_traffic_is_the_issues():
    w = _load("workloads", CELL + ".json")
    cfg = _load("configs", CONFIG + ".json")
    tr = w["traffic"]
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.8, "min": 128, "max": 6144}
    assert tr["output_len"] == {"dist": "lognormal", "median": 384,
                                "sigma": 0.7, "min": 64, "max": 1536}
    assert tr["shared_prefix"]["share"] == 0.0 and tr["bursts"] is None
    assert tr["order_seed"] == 0 and w["kind"] == "serve_hybrid"
    assert w["chips"] == 1 and w["trace_seconds"] == 1
    reqs = traffic.open_loop_schedule(tr, cfg["vocab_size"], 2 ** 31 + 57,
                                      30.0)
    assert len(reqs) >= 60
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= 128 and max(lens) <= 6144
    assert max(int(r.prompt.max()) for r in reqs) > 65536   # whole vocabulary
    e = cfg["system"]["engine"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= e["max_seq_len"]
    assert max(lens) > e["prefill_buckets"][-1]  # chunks that resume are real
    # about a quarter of what a request puts through the layers is decode
    share = sum(r.max_new for r in reqs) / (
        sum(lens) + sum(r.max_new for r in reqs))
    assert 0.18 < share < 0.35
    knee = w["knee"]
    assert knee["table"] and knee["requests_per_s"] > 0
    assert tr["rate_rps"] == pytest.approx(
        knee["requests_per_s"] * knee["times_the_knee"])


def test_the_runners_shapes():
    spec = harness.Spec(CELL)
    shapes = runner._kernel_shapes(spec, {})
    assert "moe" not in shapes and "mla" not in shapes
    assert shapes["moe_relu2"] == dict(RELU2, traced=None)
    assert shapes["ranged"]["layers"] == {"full": {"count": 1, "heads": 32}}
    assert (shapes["ranged"]["kv_heads"], shapes["ranged"]["head_dim"],
            shapes["ranged"]["traced"]) == (2, 128, None)
    traced = dict.fromkeys(runner._WINDOW_COUNTERS, 7)
    shapes = runner._kernel_shapes(spec, traced)
    assert shapes["moe_relu2"]["traced"] == {"rows": 7, "experts_hit": 7}
    assert shapes["ranged"]["traced"] == {
        "full": {"keys_decode": 7, "keys_prefill": 7}, "rows_decode": 7}
    # the accepted reader's arithmetic takes the shape: a key is 1 kB
    assert kv_attention_cost.key_bytes(shapes["ranged"]) == 1024
    assert kv_attention_cost.traced_floor_seconds(shapes["ranged"], V5E) > 0
    assert {"state_resumes_total", "prefill_chunks_total",
            "moe_held_pairs_total"} <= set(runner._WINDOW_COUNTERS)


def test_the_benchmarks_reference_is_the_models():
    with open(os.path.join(REPO, "paddle_tpu", "models", "reference",
                           "nemotron_h.py")) as f, \
            open(os.path.join(BENCH, "lib", "reference_nemotron_h.py")) as g:
        assert f.read() == g.read()


def test_the_rehearsal_resumes_its_chunks_and_agrees_on_the_experts():
    """The cell end to end on the CPU at the rehearsal's sizes, traced: the
    check passes (router choice, logprobs, final states, every chunk but a
    prompt's first resumed); the two device readers find no device plane and
    are left out."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5700000021", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["rehearsal"] is True and line["failed"] == 0
    for name in READERS + ["serve.moe_experts_roofline_pct"]:
        assert name not in line["metrics"]
    notes = line["notes"]
    assert notes["state_resumed_chunks_pct"] > 0
    assert notes["router_agreement"] == 1.0     # float32 on both sides
    assert notes["state_first_layer_rel_err"] < 1e-4
    assert notes["state_head_median_rel_err"] < 1e-4
    correct = next(ln for ln in r.stdout.splitlines()
                   if ln.startswith("serve.correct"))
    assert "resumed_exact=True" in correct and "pairs_exact=True" in correct
    setup = next(ln for ln in r.stdout.splitlines()
                 if ln.startswith("serve.setup"))
    assert '"kv": 1' in setup and '"state": 3' in setup
