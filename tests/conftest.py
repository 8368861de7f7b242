"""Test harness config.

Tests run on a virtual 8-device CPU mesh (the reference tests distributed code
multi-process on one host, test_dist_base.py:783; we test multi-chip SPMD with
XLA's forced host device count instead). Must run before jax creates backends.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

import threading
import time

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "thread_leak_ok: opt out of the non-daemon thread-leak guard "
        "(tests that intentionally leave a joinable thread behind)")


def pytest_collection_modifyitems(items):
    """One assertion of one benchmark test pins what a later PR must move:
    ``tests/bench/test_sparse_cells.py::
    test_every_new_reader_is_listed_for_this_cell_alone`` (PR 44) reads "the
    LAST cell, the LAST configuration and the last cell of
    ``serve_tokens_per_s``'s list are GLM-5.2's", and the contract a PR is held
    to puts every new entry of ``BENCHMARK.json`` at the END of its list (PR 46
    added ``brumby-14b-d8`` there). That file is the benchmark's and a PR of
    another kind may not edit it, nor ``tests/bench/conftest.py`` (PERF.md
    section 7, for the next ``benchmark`` PR: drop the three ``[-1]``). Until
    then the test MUST fail, and by an assertion: ``strict`` turns a pass into
    a failure, so the PR that mends it has to delete this hook, and
    ``tests/bench/test_retention_cells.py::
    test_glm_readers_still_list_their_cell_alone`` holds every other
    assertion of it meanwhile."""
    for item in items:
        if item.nodeid.endswith(
                "test_sparse_cells.py::"
                "test_every_new_reader_is_listed_for_this_cell_alone"):
            item.add_marker(pytest.mark.xfail(
                reason="pins GLM-5.2's cell as the last of BENCHMARK.json",
                raises=AssertionError, strict=True))


@pytest.fixture(autouse=True)
def _no_thread_leaks(request):
    """Every test must clean up its non-daemon threads: a leaked joinable
    thread holds the interpreter open at exit and poisons later tests'
    lockdep/leak accounting. Daemon threads (named pt-*) are the
    runtime's long-lived workers and are exempt by design."""
    before = set(threading.enumerate())
    yield
    if request.node.get_closest_marker("thread_leak_ok"):
        return
    # teardown grace: threads mid-join finish within a short window
    deadline = time.time() + 2.0
    while time.time() < deadline:
        leaked = [t for t in threading.enumerate()
                  if t not in before and not t.daemon and t.is_alive()]
        if not leaked:
            return
        time.sleep(0.05)
    pytest.fail(
        f"test leaked non-daemon thread(s): "
        f"{[t.name for t in leaked]} — join them in teardown or mark "
        f"the test @pytest.mark.thread_leak_ok", pytrace=False)
