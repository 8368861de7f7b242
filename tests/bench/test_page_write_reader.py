"""``serve.page_write_pct``: its arithmetic on hand-made spans, what it makes
of prefill calls that do not say (the parent), the argument as a real trace
carries it, the engine's two counters in ``stats()``, and the entry in
``BENCHMARK.json``."""
import json
import os

import numpy as np
import pytest

from benchmark.lib import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "serve.page_write_pct"
CELLS = ("openpangu-ultra-moe-d5e16.doc-qa-peak",
         "laguna-xs2-d5.mixed-context-peak")


@pytest.mark.parametrize("calls,want", [
    ([(2048, 16), (2048, 16), (512, 4)], 100.0),      # all pages
    ([(16, 0), (64, 0)], 0.0),                        # none: row programs
    ([(2048, 16), (64, 0), (448, 0)], 80.0),          # by tokens, not by calls
    ([(2048, 16), (2048, None), (512, 0)], 80.0),     # one that does not say
    ([(2048, None), (512, None)], None),              # the parent
    ([], None)])                                      # no call in the window
def test_the_share_is_tokens_written_as_pages_over_prefill_tokens(calls, want):
    got = harness.read_layer_metric(NAME).share_pct(calls)
    assert got == (want if want is None else pytest.approx(want))


def test_spans_without_the_argument_read_as_nothing():
    """A v5e trace of the parent's worker (PR 24's recording): no prefill
    call that says how it wrote."""
    mod = harness.read_layer_metric(NAME)
    calls = mod.calls(os.path.join(
        REPO, "benchmark", "testdata", "v5e_serve_rounds.xplane.pb"),
        0, float("inf"))
    assert {p for _w, p in calls} <= {None}
    assert mod.share_pct(calls) is None


@pytest.mark.parametrize("shapes", [{"kind": "train"},
                                    {"kind": "serve", "chips": 1}, {}])
def test_no_trace_no_number(shapes):
    mod = harness.read_layer_metric(NAME)
    assert mod.reduce(None, {"kv_pages_written_total": 3}, {}, shapes) is None


def test_the_argument_reaches_the_trace(tmp_path):
    """``span(..., W=w, pages=n)`` are stats of the event the profiler
    writes; only prefill calls are read."""
    import jax

    from paddle_tpu.observability.trace import span

    jax.profiler.start_trace(str(tmp_path))
    for name, args in (("pt.serve.decode_round", dict(W=1, pages=9)),
                       ("pt.serve.prefill_chunk", dict(W=256, pages=2)),
                       ("pt.serve.prefill_chunk", dict(W=64, pages=0)),
                       ("pt.serve.prefill_chunk", dict(W=128))):  # a parent's
        with span(name, **args):
            pass
    jax.profiler.stop_trace()
    from benchmark.lib import xplane

    mod = harness.read_layer_metric(NAME)
    calls = mod.calls(xplane.find_xplane(str(tmp_path)), 0, float("inf"))
    assert sorted(calls, key=lambda c: c[0]) == [(64, 0), (128, None),
                                                 (256, 2)]
    assert mod.share_pct(calls) == pytest.approx(80.0)


def test_stats_hold_both_counters_and_the_span_says_the_pages():
    """A tiny Laguna engine: a prompt of two whole pages and one inside a
    page; the counters and every ``pt.serve.prefill_chunk`` span's ``pages``
    agree, and the reader's share of them is the tokens'."""
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
    from paddle_tpu.observability.trace.request_trace import tracer

    paddle.seed(11)
    cfg = LagunaConfig.tiny()
    eng = serving.GenerationEngine(
        LagunaForCausalLM(cfg), serving.GenerationConfig(
            max_slots=2, max_seq_len=64, page_len=8,
            prefill_buckets=(4, 16), prefix_cache=False))
    rng = np.random.default_rng(3)
    with eng:
        for n in (16, 3):
            eng.submit(rng.integers(0, cfg.vocab_size, n),
                       max_new_tokens=2).result(timeout=300)
        counters = eng.stats()["counters"]
    assert counters["kv_pages_written_total"] == 2
    assert counters["kv_rows_written_total"] >= 4
    said = [(r["args"]["W"], r["args"]["pages"])
            for r in tracer().worker_spans()
            if r["thread"].endswith(eng.name)
            and r["name"] == "pt.serve.prefill_chunk"]
    assert sorted(said) == [(4, 0), (16, 2)]
    assert harness.read_layer_metric(NAME).share_pct(said) == \
        pytest.approx(80.0)


def test_the_entry_lists_the_two_cells_that_write_pages():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1
    assert sorted(entry[0]["workloads"]) == sorted(CELLS)
    assert entry[0]["moves"] == "serve_tokens_per_s"
    assert entry[0]["source"] == "program_span"
    assert entry[0]["layer"] == "scheduler and cache"
