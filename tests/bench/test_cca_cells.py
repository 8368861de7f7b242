"""The cell whose model keeps memory of TWO kinds in every layer (K/V pages and
a conv tail by slot: compressed convolutional attention) behind a top-1 expert
sublayer: the configuration's file against the catalog row and the config
class, the traffic, the ``serve_cca`` runner's shapes, its one new reader on a
made-up trace and on a program that lacks the scope, the reference copy, and
the rehearsal end to end on the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import (harness, kv_attention_cost, mhc_cost, moe_cost,
                           part_time, peaks, traffic)
from benchmark.runners import serve_cca as runner
from benchmark.runners import serve_hybrid

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
V5E = peaks.peaks_for("TPU v5 lite")
CONFIG = "zaya1-8b-d20"
CELL = CONFIG + ".math-reasoning-peak"
BEFORE = "nemotron-3-nano-30b-a3b-d9.agent-reasoning-peak"
READER = "serve.cca_mix_share_pct"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CHIP_BYTES = 16_909_336_064


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _reader(name):
    return harness.read_layer_metric(name)


def test_the_mixings_scope_is_read_kernel_or_not(monkeypatch):
    """Ops whose own name stack holds ``pt.cca_mix`` — the grouped conv's
    matmuls and the elementwise mixing alike — by self time over busy time;
    the projections around them in the same ``attn_proj`` are not theirs."""
    stacks = ["jit(pt_window1)/pt.attn_proj/pt.cca_mix/dot_general:",
              "jit(pt_prefill2048_carry)/pt.attn_proj/pt.cca_mix/mul:",
              "jit(pt_prefill2048_carry)/pt.attn_proj/dot_general:", "",
              "jit(pt_window1)/pt.mlp/pt.router/pt.experts/gmm:"]
    from paddle_tpu.observability.trace.parts import SUBPARTS

    own = [part_time.part_of(s, SUBPARTS) for s in stacks]
    assert own == ["cca_mix", "cca_mix", None, None, None]
    ops = [(own[0], 0.0, 100.0), (own[1], 100.0, 250.0),
           (own[2], 250.0, 650.0), (own[3], 650.0, 700.0),
           (own[4], 800.0, 1000.0)]
    assert mhc_cost.scope_ns([(ops, [(0.0, 1000.0)])], "cca_mix", 0.0,
                             1000.0) == (250.0, 900.0)
    monkeypatch.setattr(mhc_cost, "traced_scope_ns",
                        lambda shapes, name: (3e6, 60e6)
                        if name == "cca_mix" else None)
    assert _reader(READER).reduce(None, {}, {}, {"kind": "serve"}) == 5.0


def test_the_reader_finds_nothing_in_a_program_that_lacks_the_scope():
    """The parent has no such scope: the reader returns ``None`` and does not
    raise (a train cell's shapes, a serve cell of another model, an untraced
    run of this one)."""
    mod = _reader(READER)
    for shapes in ({"kind": "train"}, {"kind": "serve", "chips": 1}, {},
                   runner._kernel_shapes(harness.Spec(CELL), {})):
        assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_new_entries_stand_behind_what_was_there():
    bench = _load("..", "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index(BEFORE)
    before = set(names[:names.index(CELL)])
    cell = bench["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "math-reasoning-peak", 1)
    assert len(cell["why"]) <= 200
    assert cell["why"] == _load("workloads", CELL + ".json")["why"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   for k in entry["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    by = {m["name"]: m for m in bench["per_layer"]}
    # the new reader is no entry: ``test_train_parts.py`` pins the list's end
    # and the middle is not a PR's to write to; the runner reads it into a
    # traced run's notes
    assert READER not in by and (READER,) == runner.NOTED_READERS
    assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                       READER + ".py"))
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"].index(CELL) > tokens["workloads"].index(BEFORE)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    joined = {
        "serve.gen_late_p95_ms", "serve.queue_wait_p95_ms",
        "serve.occupancy_pct", "serve.worker_idle_pct", "serve.run_ahead_pct",
        "serve.prefill_chunk_p50_ms", "serve.state_install_p50_ms",
        "serve.moe_experts_share_pct", "serve.moe_experts_roofline_pct",
        "serve.ranged_attention_share_pct",
        "serve.ranged_attention_roofline_pct"} | {
        f"serve.part_{p}_share_pct" for p in (
            "attn_proj", "mlp", "norm", "head", "cache_write", "attention",
            "unscoped")}
    assert joined <= mine       # later PRs may add readers that list it
    for name in joined:     # behind the cells that were there, wherever
        cells = by[name]["workloads"]       # later cells come to stand
        there = [cells.index(c) for c in cells if c in before]
        assert cells.index(CELL) > max(there), name
    # the lists tests pin to other cells stay theirs
    for name in ("serve.state_resumed_chunks_pct",
                 "serve.part_router_share_pct",
                 "serve.part_experts_share_pct", "serve.carried_rounds_pct"):
        assert CELL not in by[name]["workloads"], name
    assert len(names) == len(set(names))


def test_the_configuration_holds_every_catalog_key_and_states_its_cut():
    """Every key of the catalog row's ``config`` is in the file unchanged but
    the two under ``reduced``, each with published / here / why; no width, no
    expert, no head and no vocabulary row is among them; the arithmetic of
    the cut is the config class's own shapes."""
    cfg = _load("configs", CONFIG + ".json")
    entry = next(c for c in _load("..", "BENCHMARK.json")["configs"]
                 if c["name"] == CONFIG)
    reduced = cfg["reduced"]
    assert list(reduced) == entry["reduced"]
    assert cfg["source"] == entry["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "ZAYA1-8B")
        assert cfg["source"] == row["source_url"]
        for key, want in row["config"].items():
            if key in reduced:
                assert cfg[key] != want and reduced[key]["why"]
            else:
                assert cfg[key] == want, key
        assert reduced["num_hidden_layers"]["published"] == \
            row["config"]["num_hidden_layers"] == 40
        assert row["config"]["layer_types"] == ["hybrid"] * 40
    assert reduced["num_hidden_layers"]["here"] == \
        cfg["num_hidden_layers"] == 20
    assert cfg["layer_types"] == ["hybrid"] * 20
    # every published width
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["cca_time0"],
            cfg["cca_time1"], cfg["router_hidden_size"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["vocab_size"]) == (2048, 8, 2, 128, 2, 2, 256, 16, 2048, 1,
                                   262272)
    assert "first of 2 pipeline stages" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 9
    for word in ("shifted", "no bias", "tau", "rotate_half", "gelu",
                 "own probability", "four learned", "MoD", "neutral"):
        assert any(word in a for a in cfg["assumed"]), word
    e = cfg["system"]["engine"]
    assert (e["max_seq_len"], e["page_len"], e["prefill_buckets"],
            e["prefix_cache"]) == (6144, 128, [256, 1024, 2048], False)
    assert e["max_slots"] in (128, 256)   # 2048 + slots rows: whole gmm tiles
    # the model's config class takes the file's keys letter for letter
    from benchmark.runners.serve_recurrent import model_config

    mc = model_config(cfg)
    assert (mc.q_dim, mc.mix_dim, mc.tail_dim, mc.rotary_dim, mc.rope_theta,
            mc.dtype) == (1024, 1280, 2688, 64, 5e6, "bfloat16")
    sm = mc.served_model()
    assert sm.cache_spec == {"kind": "kv_by_layer",
                             "layers": ["full+state"] * 20}
    assert sm.carries_rounds and sm.resumes_state
    assert sm.state_spec["tail"][0] == (2688,)
    import jax

    from paddle_tpu.models import zaya1

    # the file's arithmetic, from the class's own shapes
    shapes = zaya1.layer_shapes(mc)
    count = {k: int(np.prod(s)) for k, (s, _dt) in shapes.items()}
    experts = sum(v for k, v in count.items() if k.startswith("experts_"))
    par = cfg["parameters"]
    assert experts / 1e6 == pytest.approx(par["experts_of_a_layer_M"],
                                          abs=0.01)
    assert (sum(count.values()) - experts) / 1e6 == pytest.approx(
        par["layer_outside_experts_M"], abs=0.001)
    embed = cfg["vocab_size"] * cfg["hidden_size"]
    here = 20 * sum(count.values()) + embed + cfg["hidden_size"]
    assert here / 1e6 == pytest.approx(par["here_M"], abs=0.1)
    assert here * 2 / 1e9 == pytest.approx(par["here_GB_bfloat16"], abs=0.001)
    n = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in
            jax.tree_util.tree_leaves(sm.param_shapes()))
    # as stored: the float32 router, depthwise conv and scales at 4 B
    assert 0 < n / 1e9 - par["here_GB_bfloat16"] < 0.04
    # weights, pool and tails fill at least 80 % of the chip
    pool = 20 * 2 * e["num_pages"] * 2 * 128 * 128 * 2
    tails = 20 * e["max_slots"] * 2688 * 4
    assert (n + pool + tails) / CHIP_BYTES > 0.80
    # ... and the check sends as many requests as the pool holds TOGETHER
    # (a checked request's tail is read from its slot's row afterwards): the
    # pages bind before the slots, as under the cell's traffic
    w = _load("workloads", CELL + ".json")
    n = runner.together(e, w["traffic"])
    assert 0.75 * e["max_slots"] <= n <= e["max_slots"]
    lens = traffic.lognormal_quantiles(n, w["traffic"]["prompt_len"]) + \
        traffic.lognormal_quantiles(n, w["traffic"]["output_len"])[::-1]
    assert sum(-(-int(t) // 128) for t in lens) <= e["num_pages"] - 1
    assert len(range(0, n, w["check_every"])) == 8
    reh = harness.Spec(CELL, rehearsal=True)
    assert runner.together(reh.config["system"]["engine"],
                           reh.workload["traffic"]) == 4


def test_the_traffic_is_the_issues():
    w = _load("workloads", CELL + ".json")
    cfg = _load("configs", CONFIG + ".json")
    tr = w["traffic"]
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 384,
                                "sigma": 0.8, "min": 64, "max": 2048}
    assert tr["output_len"] == {"dist": "lognormal", "median": 512,
                                "sigma": 0.7, "min": 64, "max": 3072}
    assert tr["shared_prefix"]["share"] == 0.0 and tr["bursts"] is None
    assert tr["order_seed"] == 0 and w["kind"] == "serve_cca"
    assert w["chips"] == 1 and w["trace_seconds"] == 1
    reqs = traffic.open_loop_schedule(tr, cfg["vocab_size"], 2 ** 31 + 59,
                                      30.0)
    assert len(reqs) >= 60
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= 64 and max(lens) <= 2048
    assert max(int(r.prompt.max()) for r in reqs) > 131072  # whole vocabulary
    e = cfg["system"]["engine"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= e["max_seq_len"]
    # more than half of what a request puts through the layers is decode
    share = sum(r.max_new for r in reqs) / (
        sum(lens) + sum(r.max_new for r in reqs))
    assert 0.5 < share < 0.7
    knee = w["knee"]
    assert knee["table"] and knee["requests_per_s"] > 0
    assert tr["rate_rps"] == pytest.approx(
        knee["requests_per_s"] * knee["times_the_knee"])
    # the rehearsal's prompts all pass the largest rehearsal bucket, at a
    # rate that puts several admissions into every second: a chunk, a
    # resumed chunk, an install and a round in the traced second
    reh = harness.Spec(CELL, rehearsal=True)
    assert reh.workload["traffic"]["prompt_len"]["min"] > \
        max(reh.config["system"]["engine"]["prefill_buckets"])
    assert reh.workload["traffic"]["rate_rps"] >= 8


def test_the_runners_shapes():
    spec = harness.Spec(CELL)
    shapes = runner._kernel_shapes(spec, {})
    assert shapes["moe"] == {"hidden": 2048, "width": 2048, "itemsize": 2,
                             "traced": None}
    assert shapes["ranged"]["layers"] == {"full": {"count": 20,
                                                   "heads": 160}}
    assert (shapes["ranged"]["kv_heads"], shapes["ranged"]["head_dim"],
            shapes["ranged"]["traced"]) == (2, 128, None)
    traced = dict.fromkeys(serve_hybrid._WINDOW_COUNTERS, 7)
    shapes = runner._kernel_shapes(spec, traced)
    assert shapes["moe"]["traced"] == {"rows": 7, "experts_hit": 7}
    # every layer pages: the engine's keys, once a layer
    assert shapes["ranged"]["traced"] == {
        "full": {"keys_decode": 140, "keys_prefill": 140}, "rows_decode": 7}
    # the accepted readers' arithmetic takes the shapes: a key is 1 kB a
    # layer, an expert streams 3 x 2048 x 2048 x 2 B
    assert kv_attention_cost.key_bytes(shapes["ranged"]) == 1024
    assert kv_attention_cost.traced_floor_seconds(shapes["ranged"], V5E) > 0
    cost = moe_cost.gmm_cost(16, 16, shapes["moe"])
    assert cost["bytes"] > 16 * 3 * 2048 * 2048 * 2 and cost["flops"] == \
        2 * 16 * 3 * 2048 * 2048
    assert runner.Server is serve_hybrid.Server


def test_the_benchmarks_reference_is_the_models():
    with open(os.path.join(REPO, "paddle_tpu", "models", "reference",
                           "zaya1.py")) as f, \
            open(os.path.join(BENCH, "lib", "reference_zaya1.py")) as g:
        assert f.read() == g.read()


def test_one_dense_pass_of_the_served_blocks_chooses_as_the_reference():
    """``served_choices``: the check's own pass of ``block_fn`` over a whole
    sequence — the experts it reads off the router representation the blocks
    hand on are the reference's, the tails after the first ``n`` tokens too
    (the later positions are padding)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Zaya1Config, Zaya1ForCausalLM, zaya1
    from paddle_tpu.models.reference import zaya1 as ref

    cfg = Zaya1Config.tiny()
    paddle.seed(3)
    model = Zaya1ForCausalLM(cfg)
    params = model.served_model().params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 43)
    _y, chosen, tails = ref.final_hidden(get, zaya1.as_dict(cfg), ids, 37)
    mine, held = runner.served_choices(cfg, params, ids, block=8, n=37)
    assert chosen.shape == mine.shape == (cfg.num_hidden_layers, 43, 1)
    assert (np.asarray(mine) == chosen).all()
    assert len(np.unique(chosen)) >= 6          # top-1 of 8: spread
    for got, want in zip(held, tails):
        assert got["tail"].shape == (1, cfg.tail_dim)
        np.testing.assert_allclose(np.asarray(got["tail"][0]),
                                   np.asarray(want["tail"]), atol=2e-4)


def test_the_rehearsal_resumes_its_tails_and_agrees_on_the_experts():
    """The cell end to end on the CPU at the rehearsal's sizes, traced: the
    check passes (router choice, logprobs, final tails, every chunk but a
    prompt's first resumed); the device readers find no device plane and are
    left out."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5900000021", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["rehearsal"] is True and line["failed"] == 0
    for name in (READER, "serve.moe_experts_roofline_pct",
                 "serve.ranged_attention_roofline_pct"):
        assert name not in line["metrics"]
    assert {"serve.prefill_chunk_p50_ms", "serve.state_install_p50_ms",
            "serve.run_ahead_pct"} <= set(line["metrics"])
    notes = line["notes"]
    assert notes["state_resumed_chunks_pct"] > 0
    assert notes["router_agreement"] == 1.0     # float32 on both sides
    assert notes["router_alone_agreement"] == 1.0
    assert notes["tail_first_layer_rel_err"] < 1e-5
    assert notes["tail_median_rel_err"] < 1e-5
    correct = next(ln for ln in r.stdout.splitlines()
                   if ln.startswith("serve.correct"))
    assert "resumed_exact=True" in correct and "pairs_exact=True" in correct
    setup = next(ln for ln in r.stdout.splitlines()
                 if ln.startswith("serve.setup"))
    assert '"kv": 3' in setup and '"state": 3' in setup
    assert '"full": 3' in setup
