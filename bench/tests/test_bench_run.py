"""The command's refusals, and that BENCHMARK.json finds every part of
every cell by name."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import cells, correct
from bench.tests.test_bench_families import assert_tree_is_the_programs

ROOT = cells.ROOT


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "llada8b-chat-c8",
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_finds_its_parts():
    b = _bench()
    for w in b["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.chips == w["chips"] == 1
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cells.load_reader(m["name"]))
        dims = cells.model_dims(cell.config)
        assert dims["family"] == cell.config["reference"]
        assert dims["heads"] % dims["kv_heads"] == 0
        # the weights the benchmark makes fit the program's tree
        assert_tree_is_the_programs(cell.config)
        # every limit names a number the check computes
        limits = set(cell.config["limits"])
        assert limits and limits <= set(correct.readings(np.zeros(1)))


def test_metric_entries_follow_the_rules():
    b = _bench()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cellnames = {w["name"] for w in b["workloads"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert name.match(m["name"]) and m["source"] in ("host_clock",
                                                          "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert name.match(m["name"]) and m["moves"] in e2e
        for w in m.get("workloads", cellnames):
            assert w in cellnames
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cellnames)
    assert {p for p in b["paths"]} == {"bench"}


def test_peaks_are_keyed_by_device_kind():
    assert cells.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(cells.CellError):
        cells.peaks("TPU v9 imaginary")
