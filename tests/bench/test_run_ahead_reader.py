"""``serve.run_ahead_pct``: its arithmetic on plain values, what it makes of
a program that does not say, and the argument as a real trace carries it."""
import os

import pytest

from benchmark.lib import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "serve.run_ahead_pct"


@pytest.mark.parametrize("flags,want", [
    ([1, 1, 1, 0], 75.0),       # three of four programs went out ahead
    ([0, 0], 0.0),              # below the knee: read, then decide
    ([1, None, 0, None], 50.0),  # spans that do not say are not programs
    ([], None), ([None, None], None)])  # an empty window; the parent
def test_the_share_is_programs_run_ahead_over_programs(flags, want):
    assert harness.read_layer_metric(NAME).share_pct(flags) == want


def test_spans_without_the_argument_read_as_nothing():
    """A v5e trace of the parent's worker (PR 24's recording): its program
    spans carry ``n_active`` and ``W`` and no ``ahead``."""
    mod = harness.read_layer_metric(NAME)
    flags = mod.ahead_flags(os.path.join(
        REPO, "benchmark", "testdata", "v5e_serve_rounds.xplane.pb"),
        0, float("inf"))
    assert len(flags) >= 3 and set(flags) == {None}
    assert mod.share_pct(flags) is None


@pytest.mark.parametrize("shapes", [{"kind": "train"},
                                    {"kind": "serve", "chips": 1}, {}])
def test_no_trace_no_number(shapes):
    """A train cell, an untraced run, a process that is not the benchmark's
    command: ``None``, and no exception."""
    mod = harness.read_layer_metric(NAME)
    assert mod.reduce(None, {"decode_steps": 3}, {}, shapes) is None


def test_the_argument_reaches_the_trace(tmp_path):
    """``span(..., ahead=1)`` is a stat of the event the profiler writes."""
    import jax

    from paddle_tpu.observability.trace import span

    jax.profiler.start_trace(str(tmp_path))
    for name, ahead in (("pt.serve.decode_round", 1),
                        ("pt.serve.prefill_chunk", 0),
                        ("pt.serve.prefill_chunk", 1),
                        ("pt.serve.emit", 1)):  # not a program
        with span(name, ahead=ahead):
            pass
    jax.profiler.stop_trace()
    from benchmark.lib import xplane

    mod = harness.read_layer_metric(NAME)
    flags = mod.ahead_flags(xplane.find_xplane(str(tmp_path)),
                            0, float("inf"))
    assert sorted(flags) == [0, 1, 1]
    assert mod.share_pct(flags) == pytest.approx(200 / 3)
