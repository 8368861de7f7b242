"""The retention cell's benchmark arithmetic, readers, configuration and
check, on the CPU: what one ``pt_retention_step`` / ``pt_retention_chunk``
call costs (``benchmark/lib/retention_cost.py``, worked by hand here), that
each new reader finds nothing in a program that lacks what it reads, that the
configuration is the catalog's row but for what it lists, and what the state
half of ``runners/serve_retention.py``'s ``correct`` sees."""
import importlib.util
import json
import os

import numpy as np
import pytest

from benchmark.lib import harness, peaks, retention_cost

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "brumby-14b-d8.doc-reasoning-peak"
V5E = peaks.PEAKS["TPU v5 lite"]
SHAPE = {"heads": 40, "kv_heads": 8, "d": 128, "c": 128, "layers": 8}
NEW = ["serve.retention_step_share_pct", "serve.retention_step_roofline_pct",
       "serve.retention_chunk_share_pct",
       "serve.retention_chunk_roofline_pct", "serve.state_resumed_chunks_pct"]


def test_the_cost_counts_the_minimal_phi_whatever_the_kernel_tiles_to():
    assert retention_cost.phi_dim(128) == 8256
    # 20 rows x 8 heads x (8256 x 128 + 8256) x 4 B, in and out
    state = 20 * 8 * (8256 * 128 + 8256) * 4
    assert state == 681_615_360
    small = 4 * (2 * 20 * 40 * 128 + 2 * 20 * 8 * 128 + 20 * 8)
    assert retention_cost.step_bytes(20, 40, 8, 128) == 2 * state + small


def test_a_decode_step_is_bound_by_the_states_bytes():
    f = retention_cost.step_floor_seconds(SHAPE, 20, V5E)
    assert f["bound"] == "bytes"
    # 1.364 GB a layer a round at 819 GB/s
    assert f["seconds"] == pytest.approx(1.6655e-3, rel=1e-3)
    # linear in the rows: a stretch's rounds, summed, are one number
    assert retention_cost.step_floor_seconds(SHAPE, 160, V5E)["seconds"] == \
        pytest.approx(8 * f["seconds"], rel=1e-9)
    # the operations are a thirtieth of that (and the VPU's, not the MXU's)
    flops = retention_cost.step_flops(20, 40, 8, 128)
    assert flops == 20 * 8 * (8256 * 129 * 13 + 8256 * 6)
    assert flops / V5E["bf16_flops_per_s"] < f["seconds"] / 30


def test_a_prefill_chunk_is_bound_by_its_operations():
    # per token and K/V head: six contractions of 2 x 8256 x 129 (five
    # queries and the update) and the in-chunk product and sum
    per = 2 * 8256 * 129 * 6 + 5 * 128 * 2 * 128
    assert retention_cost.chunk_flops(1, 2048, 40, 8, 128, 128) == \
        2048 * 8 * per
    f = retention_cost.chunk_floor_seconds(SHAPE, 2048, 1, V5E)
    assert f["bound"] == "operations"
    assert f["seconds"] == pytest.approx(2048 * 8 * per / 197e12, rel=1e-9)
    assert 1.0e-3 < f["seconds"] < 1.2e-3
    # a call of a few tokens moves its row's state in and out: bytes
    tiny = retention_cost.chunk_floor_seconds(SHAPE, 16, 1, V5E)
    assert tiny["bound"] == "bytes"
    assert tiny["seconds"] == pytest.approx(
        (2 * 8 * (8256 * 129) * 4 + 4 * 16 * 128 * 96) / 819e9, rel=1e-9)


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_that_lacks_it(name):
    """The parent has no such kernel or counter, and an untraced run no
    trace: the reader returns ``None`` and does not raise."""
    mod = _reader(name)
    for shapes in ({"kind": "train"}, {"kind": "serve", "chips": 1}, {},
                   {"kind": "serve", "retention": dict(SHAPE, traced=None)}):
        assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_resumed_share_reader_reads_the_counters():
    mod = _reader("serve.state_resumed_chunks_pct")
    assert mod.reduce(None, {"prefill_chunks_total": 40,
                             "state_resumes_total": 25}, {}, {}) == 62.5
    assert mod.reduce(None, {"prefill_chunks_total": 40,
                             "state_resumes_total": 0}, {}, {}) == 0.0
    assert mod.reduce(None, {"prefill_chunks_total": 0}, {}, {}) is None


def test_the_runner_hands_the_readers_the_published_widths():
    from benchmark.runners import serve_retention

    spec = harness.Spec(CELL)
    shapes = serve_retention._kernel_shapes(spec, {})["retention"]
    assert {k: shapes[k] for k in SHAPE} == SHAPE and shapes["traced"] is None
    traced = dict.fromkeys(serve_retention._WINDOW_COUNTERS, 7)
    shapes = serve_retention._kernel_shapes(spec, traced)["retention"]
    assert shapes["traced"] == {"step_rows": 7, "chunk_tokens": 7,
                                "chunk_calls": 7 * 8}


def test_the_configuration_is_the_catalog_rows_but_for_what_it_lists():
    with open(os.path.join(BENCH, "configs", "brumby-14b-d8.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog in this installation")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    assert cfg["source"] == row["source_url"]
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (value, cfg[key]) == (40, 8)
            assert cfg["reduced"][key]["published"] == value
        else:
            assert cfg[key] == value, key
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "brumby-14b-d8")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert not any(k.endswith(("_size", "_dim", "_rank"))
                   for k in entry["reduced"])
    e = cfg["system"]["engine"]
    assert e["max_seq_len"] == 17408 <= cfg["max_position_embeddings"]
    assert e["prefill_buckets"] == [256, 512, 2048] and not e["prefix_cache"]


def test_the_cell_is_listed_as_the_issue_names_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-d8", "doc-reasoning-peak", 1)
    assert len(cell["why"]) <= 200
    # new entries go BEHIND what was there (no pin on being the last: the
    # next cell goes behind this one)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index("glm-5.2-d5e16.repo-qa-peak")
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("brumby-14b-d8") > configs.index("glm-5.2-d5e16")
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"].index(CELL) > tokens["workloads"].index(
        "glm-5.2-d5e16.repo-qa-peak")
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= mine
    assert {"serve.state_install_p50_ms", "serve.prefill_chunk_p50_ms",
            "serve.part_attention_share_pct",
            "serve.part_unscoped_share_pct"} <= mine
    # not the mixer's, the router's, the experts', the carried rounds' or
    # the page writes'
    assert not mine & {"serve.part_mixer_share_pct",
                       "serve.part_router_share_pct",
                       "serve.part_experts_share_pct",
                       "serve.carried_rounds_pct", "serve.page_write_pct",
                       "serve.ssm_step_share_pct"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        tr = json.load(f)["traffic"]
    assert (tr["prompt_len"]["median"], tr["prompt_len"]["sigma"],
            tr["prompt_len"]["min"], tr["prompt_len"]["max"]) == (
        3072, 0.8, 512, 16384)
    assert (tr["output_len"]["median"], tr["output_len"]["sigma"],
            tr["output_len"]["min"], tr["output_len"]["max"]) == (
        320, 0.6, 64, 896)
    assert tr["order_seed"] == 0 and tr["bursts"] is None


def test_glm_readers_still_list_their_cell_alone():
    """What ``test_sparse_cells.py::
    test_every_new_reader_is_listed_for_this_cell_alone`` holds besides its
    three pins on GLM-5.2's cell being the LAST (``tests/conftest.py``)."""
    spec = importlib.util.spec_from_file_location(
        "sparse_cells_for_retention", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "test_sparse_cells.py"))
    T = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(T)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in T.READERS:
        assert by[name]["workloads"] == [T.CELL], name
        assert by[name]["moves"] == "serve_tokens_per_s"
        assert ("roofline" in name) == (by[name]["better"] == "higher")
    assert T.CELL in by["serve.run_ahead_pct"]["workloads"]
    for name in ("serve.carried_rounds_pct", "serve.page_write_pct",
                 "serve.part_router_share_pct", "serve.part_experts_share_pct",
                 "serve.mla_attention_share_pct"):
        assert T.CELL not in by[name]["workloads"], name


def _layer(rng, heads=4, rows=36, d=8):
    return {"S": rng.normal(size=(heads, rows, d)).astype(np.float32),
            "z": rng.normal(size=(heads, rows)).astype(np.float32),
            "log_decay": np.array([-40.0, -0.2, -7.0, -90.0], np.float32)}


def test_state_errors_read_the_longest_memory_head_of_each_layer():
    from benchmark.runners import serve_retention as R

    rng = np.random.default_rng(0)
    want = [_layer(rng), _layer(rng)]
    got = [{k: v.copy() for k, v in layer.items()} for layer in want]
    got[0]["z"][1] *= 1.01      # the head whose log_decay is nearest 0
    got[1]["z"][3] *= 1.04      # a head that forgets: only the worst sees it
    got[1]["S"][1] *= 1.02      # and so with S: the long-memory limit is z's
    worst, long_memory = R._state_errors(got, want)
    assert worst == pytest.approx(0.04, rel=1e-3)
    assert long_memory == [pytest.approx(0.01, rel=1e-3), 0.0]
    # the squares of z alone: the entries that only ever grow
    assert list(R._squares(36)) == [0, 8, 15, 21, 26, 30, 33, 35]
    got[0]["z"][1] = want[0]["z"][1]
    got[0]["z"][1, 1] *= 3.0    # a product x_0 x_1, not a square
    assert R._state_errors(got, want)[1][0] == 0.0


def test_a_state_kept_in_bfloat16_fails_the_long_memory_limit():
    """300 steps of a head that hardly decays, its NON-NEGATIVE entries (the
    squares of ``z``), the state rounded to bfloat16 at every write as a
    bfloat16 arena would: the rounding alone, with exact inputs, is over
    ``STATE_LONG_RTOL``; in float32 it is nowhere near."""
    import jax
    import jax.numpy as jnp

    from benchmark.runners import serve_retention as R
    from benchmark.runners.serve_recurrent import _rel_err

    rng = np.random.default_rng(1)
    steps = rng.normal(size=(300, 1, 36, 8)).astype(np.float32) ** 2
    g = np.float32(0.999)
    exact = np.zeros((1, 36, 8))
    f32 = bf16 = jnp.zeros((1, 36, 8), jnp.float32)
    for s in steps:
        exact = exact * float(g) + s.astype(np.float64)
        f32 = g * f32 + s
        bf16 = jax.lax.reduce_precision(g * bf16 + s, exponent_bits=8,
                                        mantissa_bits=7)
    assert float(_rel_err(f32, exact)[0]) < R.STATE_LONG_RTOL / 100
    assert float(_rel_err(bf16, exact)[0]) > R.STATE_LONG_RTOL


def test_another_slots_row_fails_the_coarse_state_limit():
    from benchmark.runners import serve_retention as R

    rng = np.random.default_rng(2)
    mine, theirs = [_layer(rng)], [_layer(rng)]
    worst, _long = R._state_errors(theirs, mine)
    assert worst > 10 * R.STATE_RTOL


def test_the_reference_pads_to_a_few_shapes():
    from benchmark.runners import serve_retention as R

    assert [R._pad_to(n, 17408) for n in (600, 4096, 4097, 9000, 17280)] == \
        [4096, 4096, 8192, 17408, 17408]


def test_the_benchmarks_reference_is_the_programs():
    with open(os.path.join(BENCH, "lib", "reference_brumby.py")) as f, \
            open(os.path.join(REPO, "paddle_tpu", "models", "reference",
                              "brumby.py")) as g:
        assert f.read() == g.read()
