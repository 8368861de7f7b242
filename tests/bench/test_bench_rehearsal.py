"""The benchmark rehearsed on the CPU (on-chip-measurement guide, section 2,
steps 1 and 2): every runner end to end through the SAME command, workload
file, configuration file and code path as on the chip, at the tiny sizes the
files' ``rehearsal`` groups give (four virtual devices for the mesh cell).
Checked: the last line's keys, the ``correct`` comparison against the plain
references, that a run that is not a rehearsal FAILS without a TPU, and that
``BENCHMARK.json`` keeps to the shape the driver reads.

No topology is described here (``benchmark/rehearse_aot.py`` is a script run
by hand), and every run is a child process, so this file touches no TPU
library at import or collection.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads"))
               if f.endswith(".json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _run(args, cwd=REPO, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "benchmark/run.py"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def _last_line(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsed_end_to_end(cell, trace):
    r = _run(["--workload", cell, "--seed", "3000000019", "--seconds", "2",
              "--trace", str(trace), "--rehearsal"])
    assert r.returncode == 0, r.stderr[-2000:]
    line = _last_line(r.stdout)
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line, key
    assert line["correct"] is True, r.stdout[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        chips = json.load(f)["chips"]
    assert line["device"]["count"] == chips
    assert line["metrics"], "a run reports at least one metric"
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"], name
    bench = _bench()
    if cell in [w["name"] for w in bench["workloads"]]:
        listed = bench["per_layer" if trace else "end_to_end"]
        mine = {m["name"] for m in listed
                if "workloads" not in m or cell in m["workloads"]}
        assert set(line["metrics"]) <= mine
        if not trace:  # every end-to-end metric of the cell is there
            assert set(line["metrics"]) == mine
    if not trace:
        assert "setup_s" in line["metrics"]
        assert line["metrics"]["setup_s"]["value"] > 0


def test_a_measurement_fails_without_a_tpu():
    """Not a rehearsal and no TPU: non-zero exit, no result line."""
    r = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"correct"' not in r.stdout


def test_no_result_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the program is missing: non-zero exit, no result line."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in _bench()["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0", "--rehearsal"], cwd=str(tmp_path))
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_benchmark_json_keeps_to_the_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells) and 1 <= len(cells) <= 24
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    cfgs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(REPO, c["file"])) as f:
            held = json.load(f)
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    assert {w["config"] for w in b["workloads"]} == set(cfgs)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            held = json.load(f)
        assert held["config"] == w["config"] and held["chips"] == w["chips"]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def cells_of(m):
        return set(m.get("workloads", cells))

    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
        assert cells_of(m) <= set(cells)
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$",
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


def test_every_file_under_paths_has_a_plain_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in _bench()["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), REPO)
                assert ok.match(rel) and len(rel) <= 200, rel
