"""CPU rehearsal of the harness: it refuses to run without a TPU, finds
every cell, configuration and metric by name in `BENCHMARK.json`, and
builds every cell's `Study` from its files."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _run_py(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH="", **dict(extra_env))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "2147483701", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_fails_without_a_tpu_and_prints_no_result():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_benchmark_file_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        conf = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(conf["reduced"])
        assert all(k in conf for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["source"] == "host_clock"
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_is_found_by_name_and_builds_its_study(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    assert c.traffic["name"] == cell
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.load_reader(m["name"]))
    harness.check_configuration(c)
    study = harness.build_study(c, seed=2147483701)
    assert [s.name for s in study.specs] == c.config["apps"]
    assert study.backend == "jax"
    assert study.objective.name == c.traffic["objective"]
    assert study.budget.restarts == c.traffic["restarts"]
    assert study.budget.max_rounds == c.traffic["max_rounds"]
    for key, val in c.traffic["engine_kwargs"].items():
        assert study.budget.engine_kwargs[key] == val


def test_a_cell_is_added_by_one_file_and_one_entry(tmp_path):
    """A new cell needs `bench/workloads/<cell>.json` and a `workloads`
    entry; the harness code is not touched."""
    shutil.copytree(ROOT / "bench" / "configs", tmp_path / "bench" / "configs")
    new = json.loads((ROOT / "bench" / "workloads"
                      / "qwen2.5-32b.screen.json").read_text())
    new.update(name="paper-cnn7.screen", config="paper-cnn7")
    (tmp_path / "bench" / "workloads").mkdir()
    (tmp_path / "bench" / "workloads" / "paper-cnn7.screen.json").write_text(
        json.dumps(new))
    bench = dict(BENCH, workloads=BENCH["workloads"] + [
        {"name": "paper-cnn7.screen", "config": "paper-cnn7",
         "traffic": "screen", "chips": 1, "why": "rehearsal"}])
    c = harness.load_cell("paper-cnn7.screen", bench=bench, root=tmp_path)
    assert c.config["apps"][0] == "inception"
    argv = harness.study_argv(c, 5)
    assert argv.count("--apps") == 7 and "batch=16384" in argv
    # per-layer metrics that list their cells leave the new one alone
    assert c.per_layer == []
