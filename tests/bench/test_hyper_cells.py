"""The benchmark's arithmetic for the cell whose model mixes several residual
streams (``benchmark/lib/mhc_cost.py``) against a hand count, its two readers
on a cut trace and on a program that lacks what they read, the
``serve_hyper`` runner's shapes and agreement, the configuration's file
against the catalog row, the traffic, and the rehearsal end to end on the
CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import harness, mhc_cost, part_time, peaks, traffic
from benchmark.runners import serve_hyper as runner

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
V5E = peaks.peaks_for("TPU v5 lite")
CONFIG = "xing4.0-29b-a4b-d5"
CELL = CONFIG + ".rag-extract-peak"
MHC = {"streams": 4, "hidden": 3584, "itemsize": 4}
READERS = ["serve.mhc_share_pct", "serve.mhc_roofline_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _reader(name):
    return harness.read_layer_metric(name)


def test_a_mix_is_bound_by_its_bytes_forty_to_one():
    """One (token, sublayer): the stream of 4 x 3584 float32 read once and
    written once, the sublayer's input out and output in; the skinny
    projection and the two mixes."""
    cost = mhc_cost.mix_cost(1, MHC)
    assert cost["bytes"] == 2 * 57344 + 2 * 14336 == 143360
    assert cost["flops"] == 2 * 14336 * 24 + 2 * 14336 * 6 == 860160
    floor = mhc_cost.floor_seconds(mhc_cost.mix_cost(2176 * 10, MHC), V5E)
    assert floor["bound"] == "bytes"
    # a carried chunk's ten sublayers: 3.81 ms at 819 GB/s
    assert floor["seconds"] == pytest.approx(3.809e-3, rel=1e-3)
    by_ops = mhc_cost.mix_cost(1, MHC)["flops"] / V5E["bf16_flops_per_s"]
    assert 39 < (143360 / V5E["hbm_bytes_per_s"]) / by_ops < 41


def test_the_scope_is_read_kernel_or_not():
    """Ops whose own name stack holds ``pt.mhc`` — the Mosaic calls and the
    XLA ops around them alike, whatever part is around the scope — by self
    time; an unnamed op beside them is not theirs."""
    stacks = ["jit(pt_window1)/pt.attn_proj/pt.mhc/jit(_pre)/pt_mhc_pre:",
              "jit(pt_window1)/pt.mlp/pt.mhc/jit(_post)/reshape:",
              "jit(pt_window1)/pt.attn_proj/dot_general:", "",
              "jit(pt_window1)/pt.attention/pt.indexer/reduce:"]
    from paddle_tpu.observability.trace.parts import SUBPARTS

    own = [part_time.part_of(s, SUBPARTS) for s in stacks]
    assert own == ["mhc", "mhc", None, None, "indexer"]
    ops = [(own[0], 0.0, 100.0), (own[1], 100.0, 250.0),
           (own[2], 250.0, 650.0), (own[3], 650.0, 700.0),
           (own[4], 800.0, 1000.0)]
    runs = [(0.0, 1000.0)]
    assert mhc_cost.scope_ns([(ops, runs)], "mhc", 0.0, 1000.0) == \
        (250.0, 900.0)
    assert mhc_cost.scope_ns([(ops, runs)], "mhc", 50.0, 1000.0) == \
        (200.0, 850.0)                         # clipped to the window
    assert mhc_cost.scope_ns([(ops[2:], runs)], "mhc", 0, 1000) is None


def test_the_readers_divide_the_scopes_time(monkeypatch):
    import jax

    monkeypatch.setattr(jax.devices()[0].__class__, "device_kind",
                        "TPU v5 lite", raising=False)
    monkeypatch.setattr(mhc_cost, "traced_scope_ns",
                        lambda shapes, name: (8e6, 40e6) if name == "mhc"
                        else None)
    shapes = {"kind": "serve", "mhc": dict(MHC, traced={"mixes": 21760})}
    assert _reader(READERS[0]).reduce(None, {}, {}, shapes) == 20.0
    # 3.809 ms of floor over 8 ms of the scope's ops
    assert _reader(READERS[1]).reduce(None, {}, {}, shapes) == \
        pytest.approx(100 * 3.809e-3 / 8e-3, rel=1e-3)
    # no mix ran under the profiler: no roofline
    idle = {"kind": "serve", "mhc": dict(MHC, traced={"mixes": 0})}
    assert _reader(READERS[1]).reduce(None, {}, {}, idle) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_program_that_lacks_it(name):
    """The parent has no such scope or counter: the reader returns ``None``
    and does not raise (a train cell's shapes, a serve cell of another model,
    an untraced run of this one)."""
    mod = _reader(name)
    for shapes in ({"kind": "train"}, {"kind": "serve", "chips": 1}, {},
                   {"kind": "serve", "mhc": dict(MHC, traced=None)}):
        assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_new_entries_stand_behind_what_was_there():
    bench = _load("..", "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index(
        "dots3-note-prev-d5e16.transcript-notes-peak")
    cell = bench["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "rag-extract-peak", 1)
    assert len(cell["why"]) <= 200
    assert cell["why"] == _load("workloads", CELL + ".json")["why"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_nextn_predict_layers"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert CELL in by[name]["workloads"], name
        assert by[name]["moves"] == "serve_tokens_per_s"
        assert by[name]["source"] == "device_trace"
    assert by[READERS[0]]["layer"] == by["serve.indexer_share_pct"]["layer"]
    assert by[READERS[1]]["layer"] == \
        by["serve.mla_attention_roofline_pct"]["layer"]
    assert (by[READERS[0]]["better"], by[READERS[1]]["better"]) == \
        ("lower", "higher")
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"].index(CELL) > tokens["workloads"].index(
        "dots3-note-prev-d5e16.transcript-notes-peak")
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"serve.mla_attention_share_pct", "serve.mla_attention_roofline_pct",
            "serve.moe_experts_share_pct", "serve.moe_experts_roofline_pct",
            "serve.prefill_chunk_p50_ms", "serve.run_ahead_pct",
            "serve.part_attention_share_pct", "serve.part_mlp_share_pct",
            "serve.part_unscoped_share_pct"} <= mine
    # the lists tests pin to other cells stay theirs
    for name in ("serve.part_router_share_pct", "serve.part_experts_share_pct",
                 "serve.carried_rounds_pct", "serve.page_write_pct"):
        assert CELL not in by[name]["workloads"], name
    assert len(names) == len(set(names)) and \
        sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_configuration_holds_every_catalog_key_and_states_its_cut():
    """Every key of the catalog row's ``config`` is in the file unchanged but
    the three under ``reduced``, each with published / here / why; no width,
    no expert and no vocabulary row is among them."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not installed here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
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
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 0)
    assert (cfg["n_routed_experts"], cfg["vocab_size"], cfg["hc_mult"],
            cfg["hc_sinkhorn_iters"]) == (64, 131072, 4, 20)
    assert cfg["parameters"]["here_GB_bfloat16"] == 8.10
    assert "first of eight pipeline stages" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 6
    e = cfg["system"]["engine"]
    assert (e["max_slots"], e["max_seq_len"], e["page_len"],
            e["prefill_buckets"], e["prefix_cache"]) == (
        128, 8448, 128, [256, 512, 2048], False)
    assert cfg["rehearsal"]["hc_mult"] == 4
    # the model's config class takes the file's keys letter for letter
    from benchmark.runners.serve_recurrent import model_config

    mc = model_config(cfg)
    assert (mc.stream_dim, mc.latent_dim, mc.dtype) == (14336, 576,
                                                        "bfloat16")
    n = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in
            __import__("jax").tree_util.tree_leaves(
                mc.served_model().param_shapes()))
    assert abs(n / 1e9 - (8.10 + 0.0734 + 0.0037)) < 0.01   # + packed, f32


def test_the_traffic_is_the_issues():
    w = _load("workloads", CELL + ".json")
    cfg = _load("configs", CONFIG + ".json")
    tr = w["traffic"]
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                "sigma": 0.8, "min": 256, "max": 8192}
    assert tr["output_len"] == {"dist": "lognormal", "median": 64,
                                "sigma": 0.7, "min": 8, "max": 256}
    assert tr["shared_prefix"]["share"] == 0.0 and tr["bursts"] is None
    assert tr["order_seed"] == 0 and w["kind"] == "serve_hyper"
    reqs = traffic.open_loop_schedule(tr, cfg["vocab_size"], 2 ** 31 + 53,
                                      30.0)
    assert len(reqs) >= 60
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= 256 and max(lens) <= 8192
    assert max(int(r.prompt.max()) for r in reqs) > 65536   # whole vocabulary
    e = cfg["system"]["engine"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= e["max_seq_len"]
    assert max(lens) > e["prefill_buckets"][-1]  # chunked prefill is real
    # ~97 % of the tokens a request puts through the layers are the prompt's
    share = sum(lens) / (sum(lens) + sum(r.max_new for r in reqs))
    assert 0.95 < share < 0.99
    knee = w["knee"]
    assert knee["table"] and tr["rate_rps"] == pytest.approx(
        knee["requests_per_s"] * (1.25 if "1.25" in knee["rate_is"] else 1.0))


def test_the_runners_shapes_and_agreement():
    spec = harness.Spec(CELL)
    shapes = runner._kernel_shapes(spec, {})
    assert shapes["mhc"] == dict(MHC, traced=None)
    assert (shapes["mla"]["heads"], shapes["mla"]["row_width"],
            shapes["mla"]["latent_dim"], shapes["mla"]["value_dim"],
            shapes["mla"]["layers"]) == (32, 640, 576, 512, 5)
    assert (shapes["moe"]["hidden"], shapes["moe"]["width"]) == (3584, 1024)
    traced = dict.fromkeys(runner._WINDOW_COUNTERS, 7)
    shapes = runner._kernel_shapes(spec, traced)
    assert shapes["mhc"]["traced"] == {"mixes": 7}
    assert shapes["moe"]["traced"] == {"rows": 7, "experts_hit": 7}
    mine = np.array([[[0, 1, 2, 3], [4, 5, 6, 7]]])
    theirs = np.array([[[3, 2, 1, 0], [4, 5, 9, 8]]])
    assert runner.agreement(mine, theirs) == 0.75   # sets, not orders


def test_the_benchmarks_reference_is_the_models():
    with open(os.path.join(REPO, "paddle_tpu", "models", "reference",
                           "xing4.py")) as f, \
            open(os.path.join(BENCH, "lib", "reference_xing4.py")) as g:
        assert f.read() == g.read()


def test_the_rehearsal_counts_the_mixes_and_agrees_on_the_experts():
    """The cell end to end on the CPU at the rehearsal's sizes, traced: the
    check passes (router choice, logprobs, the mixes counted exactly); the
    two device readers find no device plane and are left out."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5300000021", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["rehearsal"] is True and line["failed"] == 0
    assert READERS[0] not in line["metrics"]
    assert READERS[1] not in line["metrics"]
    notes = line["notes"]
    assert notes["router_agreement"] == 1.0     # float32 on both sides
    assert notes["mhc_mix_tokens"] > 0
    assert {"carried_rounds_pct", "page_write_pct"} <= set(notes)
    correct = next(ln for ln in r.stdout.splitlines()
                   if ln.startswith("serve.correct"))
    assert "mixes_exact=True" in correct and "pairs_exact=True" in correct
