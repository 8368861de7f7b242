"""The benchmark's arithmetic for the latent-attention / held-experts cell
(``benchmark/lib/mla_cost.py``, ``moe_cost.py``, ``kernel_time.py``) against
hand-worked values, the ``serve_latent`` runner's shapes, the new cells' data,
and ``BENCHMARK.json``'s contract with a vocabulary slice in ``reduced``."""
import importlib.util
import json
import os

import pytest

from benchmark.lib import kernel_time, mla_cost, moe_cost, peaks, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
V5E = peaks.peaks_for("TPU v5 lite")
MLA = {"heads": 128, "row_width": 640, "latent_dim": 576, "value_dim": 512,
       "itemsize": 2, "layers": 5}
MOE = {"hidden": 7680, "width": 2048, "itemsize": 2}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_a_decode_round_sits_just_under_the_ridge_on_the_bytes_side():
    # 128 rows of 2047 cached tokens + their own: 262 144 visible rows
    cost = mla_cost.decode_cost(128 * 2048, 128, MLA)
    assert cost["bytes"] == 262144 * 1280 + 128 * 128 * (640 + 512) * 2
    assert cost["flops"] == 2 * 128 * 262144 * 1088
    floor = mla_cost.floor_seconds(cost, V5E)
    assert floor["bound"] == "bytes"
    assert floor["seconds"] == pytest.approx(0.4558e-3, rel=1e-3)
    assert cost["flops"] / V5E["bf16_flops_per_s"] == \
        pytest.approx(0.3706e-3, rel=1e-3)


def test_a_prefill_chunk_is_bound_by_its_operations():
    # 512 tokens at offset 1024: token w sees 1024 + w + 1 rows
    keys = 512 * 1024 + 512 * 513 // 2
    cost = mla_cost.prefill_cost(keys, MLA)
    assert cost["flops"] == 2 * 128 * 655616 * 1088
    floor = mla_cost.floor_seconds(cost, V5E)
    assert floor["bound"] == "operations"
    assert floor["seconds"] == pytest.approx(0.9269e-3, rel=1e-3)
    traced = dict(MLA, traced={"keys_decode": 128 * 2048, "rows_decode": 128,
                               "keys_prefill": keys})
    assert mla_cost.traced_floor_seconds(traced, V5E) == \
        pytest.approx(5 * (0.4558e-3 + 0.9269e-3), rel=1e-3)


def test_an_expert_with_a_row_streams_its_weights():
    # a decode round: 64 rows over the 16 held experts of one layer
    cost = moe_cost.gmm_cost(64, 16, MOE)
    assert cost["flops"] == 2 * 64 * 3 * 7680 * 2048
    weights = 16 * 3 * 7680 * 2048 * 2
    assert cost["bytes"] == weights + 64 * (2 * 7680 * 2 + 2 * 2048 * 4
                                            + 2048 * 2 + 7680 * 2)
    floor = moe_cost.floor_seconds(cost, V5E)
    assert floor["bound"] == "bytes"
    assert floor["seconds"] == pytest.approx(1.8472e-3, rel=1e-3)
    # an expert nobody chose streams nothing
    assert moe_cost.gmm_cost(64, 9, MOE)["bytes"] < cost["bytes"] * 0.6


def test_kernel_seconds_are_clipped_to_the_window():
    class _Trace:
        window = (100.0, 1100.0)
        planes = {"/device:TPU:0": {"XLA Ops": [
            ('%gmm.1 = f32[8] custom-call(), '
             'custom_call_target="tpu_custom_call"', 50.0, 150.0),
            ('%gmm.2 = f32[8] custom-call(), '
             'custom_call_target="tpu_custom_call"', 500.0, 700.0),
            ('%pt_mla_paged_attention.3 = bf16[8] custom-call(), '
             'custom_call_target="tpu_custom_call"', 1000.0, 1400.0),
            ("%fusion.9 = f32[8] fusion()", 200.0, 900.0)]}}

    assert kernel_time.seconds_in_window(_Trace, "gmm") == \
        pytest.approx(250e-9)
    assert kernel_time.seconds_in_window(_Trace, "pt_mla_paged_attention") \
        == pytest.approx(100e-9)
    assert kernel_time.seconds_in_window(_Trace, "pt_ssm_step") is None
    assert kernel_time.seconds_in_window(None, "gmm") is None


@pytest.mark.parametrize("name", [
    "serve.mla_attention_share_pct", "serve.mla_attention_roofline_pct",
    "serve.moe_experts_share_pct", "serve.moe_experts_roofline_pct",
    "serve.prefill_chunk_p50_ms", "serve.moe_held_pairs_pct",
    "serve.prefix_hit_pct"])
def test_a_reader_finds_nothing_in_a_program_that_lacks_it(name):
    """The parent has no such span, counter or kernel: the reader returns
    ``None`` and does not raise (a train cell's shapes, a serve cell of a K/V
    model, an untraced run)."""
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for shapes in ({"kind": "train"}, {"kind": "serve", "chips": 1}, {}):
        assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_held_share_reader_reads_the_counters():
    from benchmark.lib import harness

    mod = harness.read_layer_metric("serve.moe_held_pairs_pct")
    assert mod.reduce(None, {"moe_pairs_total": 3200,
                             "moe_held_pairs_total": 200}, {}, {}) == 6.25


def test_the_prefix_hit_reader_reads_the_counters():
    from benchmark.lib import harness

    mod = harness.read_layer_metric("serve.prefix_hit_pct")
    assert mod.reduce(None, {"prompt_tokens_total": 6400,
                             "prefix_hit_tokens": 3840}, {}, {}) == 60.0
    # a cell whose requests share nothing: nothing to read
    assert mod.reduce(None, {"prompt_tokens_total": 6400,
                             "prefix_hit_tokens": 0}, {}, {}) is None


def test_the_runner_hands_the_readers_the_published_widths():
    from benchmark.lib import harness
    from benchmark.runners import serve_latent

    spec = harness.Spec("openpangu-ultra-moe-d5e16.doc-qa-peak")
    shapes = serve_latent._kernel_shapes(spec, {})
    assert {k: shapes["mla"][k] for k in MLA} == MLA
    assert {k: shapes["moe"][k] for k in MOE} == MOE
    assert shapes["mla"]["traced"] is None and shapes["moe"]["traced"] is None
    traced = dict.fromkeys(serve_latent._WINDOW_COUNTERS, 7)
    shapes = serve_latent._kernel_shapes(spec, traced)
    assert shapes["mla"]["traced"] == {"keys_decode": 7, "rows_decode": 7,
                                       "keys_prefill": 7}
    assert shapes["moe"]["traced"] == {"rows": 7, "experts_hit": 7}


def test_the_configuration_is_the_catalog_rows_but_for_what_it_lists():
    """Every key of the published ``config.json`` (the model-configs
    catalog's row, copied here so the test needs no file outside the repo)
    is in the configuration file unchanged except those under ``reduced``,
    each with published / here / why; no width is among them."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7680,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "sandwich_norm": True,
        "tie_word_embeddings": False, "v_head_dim": 128,
        "vocab_size": 153600}
    cfg = _load("configs", "openpangu-ultra-moe-d5e16.json")
    reduced = cfg["reduced"]
    assert set(reduced) == {"num_hidden_layers", "first_k_dense_replace",
                            "n_routed_experts", "vocab_size",
                            "num_nextn_predict_layers"}
    for key, want in published.items():
        if key in reduced:
            assert reduced[key]["published"] == want
            assert reduced[key]["here"] == cfg[key] != want
            assert reduced[key]["why"]
        else:
            assert cfg[key] == want, key
    # the floors of a cut: a whole period and four layers after the dense
    # ones, at least 8 routed experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 and cfg["router_experts"] == 256
    assert cfg["vocab_size"] * 8 >= published["vocab_size"]
    assert "16 chips share each layer" in cfg["deployment"]


def test_the_shared_prefix_mix_is_chat_steadys_but_for_the_prefix():
    steady = _load("workloads", "gpt2-large.chat-steady.json")["traffic"]
    shared = _load("workloads", "gpt2-large.shared-prefix.json")["traffic"]
    assert shared["shared_prefix"] == {"share": 0.8, "tokens": 192,
                                       "n_prefixes": 1}
    for key in steady:
        if key not in ("shared_prefix", "rate_rps"):
            assert shared[key] == steady[key], key
    reqs = traffic.open_loop_schedule(shared, 50257, 5, 30.0)
    first = [r.prompt[:192] for r in reqs if len(r.prompt) >= 192]
    same = sum((p == first[0]).all() for p in first)
    assert same == round(0.8 * len(reqs))
    assert max(len(r.prompt) for r in reqs) <= 256


def test_the_doc_qa_mix_draws_long_prompts_from_the_vocabulary_slice():
    w = _load("workloads", "openpangu-ultra-moe-d5e16.doc-qa-peak.json")
    cfg = _load("configs", "openpangu-ultra-moe-d5e16.json")
    reqs = traffic.open_loop_schedule(w["traffic"], cfg["vocab_size"],
                                      2 ** 31 + 11, 30.0)
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= 256 and max(lens) <= 3584
    assert max(int(r.prompt.max()) for r in reqs) < 19200
    e = cfg["system"]["engine"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= e["max_seq_len"]
    assert max(lens) > e["prefill_buckets"][-1]  # chunked prefill is real


def test_benchmark_json_keeps_to_the_contract_of_slices():
    """``test_bench_rehearsal.py::test_benchmark_json_keeps_to_the_contract``
    whole, with its rule on ``reduced`` as the contract words it: never a
    width (a hidden, intermediate, latent, state or projection size, a
    ``_dim`` or ``_rank``, a head size, an expansion factor, the experts a
    token) — a count of layers, of experts HELD or of vocabulary rows may
    be."""
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells) and 1 <= len(cells) <= 24
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    widths = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|"
                        r"head_size|_expand|expansion_factor|"
                        r"num_experts_per_tok|d_state|d_ssm|d_head)$")
    for c in b["configs"]:
        assert name.match(c["name"]) and len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(REPO, c["file"])) as f:
            held = json.load(f)
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert not any(widths.search(k) for k in c["reduced"]), c["reduced"]
    # what the old rule refuses and this one admits: that one key, no other
    # (tests/bench/conftest.py expects the old test to fail by it alone)
    assert [(c["name"], k) for c in b["configs"] for k in c["reduced"]
            if k.endswith(("_dim", "_rank", "_size"))] == \
        [("openpangu-ultra-moe-d5e16", "vocab_size")]
    assert {w["config"] for w in b["workloads"]} == \
        {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        held = _load("workloads", w["name"] + ".json")
        assert held["config"] == w["config"] and held["chips"] == w["chips"]
        assert held["why"] == w["why"]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert 1 <= b["run_seconds"] <= 51
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def cells_of(m):
        return set(m.get("workloads", cells))

    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert name.match(m["name"]) and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
        assert cells_of(m) <= set(cells)
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert name.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$",
                                                  m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert cells_of(m) and cells_of(m) <= cells_of(e2e[m["moves"]])
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    for c in cells:  # every cell: setup_s, another end-to-end, a per-layer
        assert any(c in cells_of(m) for m in b["end_to_end"]
                   if m["name"] != "setup_s"), c
        assert any(c in cells_of(m) for m in b["per_layer"]), c
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
