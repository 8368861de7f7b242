"""One hook, for one assertion of one test. ``test_bench_rehearsal.py::
test_benchmark_json_keeps_to_the_contract`` (PR 23) reads "a reduced key that
ends in ``_size`` names a width": a proxy for ``hidden_size`` /
``intermediate_size`` that also catches ``vocab_size``. The contract the
driver holds ``BENCHMARK.json`` to lets a configuration hold a chip's slice of
the vocabulary and says to list it in ``reduced`` (model-configs guide,
section 4), and ``openpangu-ultra-moe-d5e16`` (PR 32) does. That file is the
benchmark's and this PR may not edit it (REVIEW.md, PR 32, asks for a
one-line exemption there: left to the next ``benchmark`` PR, ``PERF.md``
section 7). Until then the test MUST fail, and by an assertion: ``strict``
turns a pass into a failure, so the PR that mends the old rule has to delete
this file, and ``test_latent_cells.py::
test_benchmark_json_keeps_to_the_contract_of_slices`` holds every assertion
of the old test meanwhile and that ``vocab_size`` of this one configuration
is the only key the old rule refuses.
"""
import pytest


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith("test_bench_rehearsal.py::"
                                "test_benchmark_json_keeps_to_the_contract"):
            item.add_marker(pytest.mark.xfail(
                reason="its `_size` rule takes vocab_size for a width",
                raises=AssertionError, strict=True))
