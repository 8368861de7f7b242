"""``serve.carried_rounds_pct``: its arithmetic on plain values, what it makes
of prefill calls that do not say, the argument as a real trace carries it, and
the number the rehearsal of the two cells that carry reports."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "serve.carried_rounds_pct"
CELLS = ("openpangu-ultra-moe-d5e16.doc-qa-peak",
         "laguna-xs2-d5.mixed-context-peak")


@pytest.mark.parametrize("rounds,carried,want", [
    (1, [3, 7, 2], 75.0),        # three steps rode a chunk, one did not
    (4, [0, 0], 0.0),            # chunks that found nobody running
    (0, [5, 0, 0, 1], 100.0),    # every decode step rode a chunk
    (2, [2, None, 0, None], 100 / 3),   # calls that do not say are left out
    (3, [], None),               # no chunk in the window: nothing to say
    (3, [None, None], None),     # the parent: the argument is not there
    (0, [0, 0], None)])          # chunks alone and nobody to carry: no step
def test_the_share_is_steps_carried_over_decode_steps(rounds, carried, want):
    got = harness.read_layer_metric(NAME).share_pct(rounds, carried)
    assert got == (want if want is None else pytest.approx(want))


def test_spans_without_the_argument_read_as_nothing():
    """A v5e trace of the parent's worker (PR 24's recording): rounds, and no
    prefill call that says what it carried."""
    mod = harness.read_layer_metric(NAME)
    rounds, carried = mod.steps(os.path.join(
        REPO, "benchmark", "testdata", "v5e_serve_rounds.xplane.pb"),
        0, float("inf"))
    assert rounds >= 3 and set(carried) <= {None}
    assert mod.share_pct(rounds, carried) is None


@pytest.mark.parametrize("shapes", [{"kind": "train"},
                                    {"kind": "serve", "chips": 1}, {}])
def test_no_trace_no_number(shapes):
    mod = harness.read_layer_metric(NAME)
    assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_argument_reaches_the_trace(tmp_path):
    """``span(..., carried=n)`` is a stat of the event the profiler writes;
    rounds are counted, other spans are not."""
    import jax

    from paddle_tpu.observability.trace import span

    jax.profiler.start_trace(str(tmp_path))
    for name, args in (("pt.serve.decode_round", dict(n_active=3)),
                       ("pt.serve.prefill_chunk", dict(carried=0)),
                       ("pt.serve.prefill_chunk", dict(carried=5)),
                       ("pt.serve.prefill_chunk", dict(carried=2)),
                       ("pt.serve.emit", dict(carried=9))):  # not a program
        with span(name, **args):
            pass
    jax.profiler.stop_trace()
    from benchmark.lib import xplane

    mod = harness.read_layer_metric(NAME)
    rounds, carried = mod.steps(xplane.find_xplane(str(tmp_path)),
                                0, float("inf"))
    assert rounds == 1 and sorted(carried) == [0, 2, 5]
    assert mod.share_pct(rounds, carried) == pytest.approx(200 / 3)


@pytest.mark.parametrize("cell", CELLS)
def test_the_rehearsal_of_a_cell_that_carries_reports_it(cell):
    """The cells of the two models that qualify, rehearsed with a trace: the
    metric is on the last line, a share of 100; and it is listed for exactly
    these cells in ``BENCHMARK.json``."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3600000113", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"][NAME]
    assert got["unit"] == "%" and 0.0 <= got["value"] <= 100.0
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1 and sorted(entry[0]["workloads"]) == sorted(CELLS)
    assert entry[0]["moves"] == "serve_tokens_per_s"
    assert entry[0]["source"] == "program_span"
