"""Host budgeting + persistent compile cache + pre-warm watchdog.

Unit half (jax-free): ``repro.launch.host`` budget derivation and the
subprocess env composition every benchmark/test child runs under.

Watchdog half: ``tests/_prewarm_child.py`` in a subprocess whose env
comes from ``budget_env`` (8 forced host devices, per-engine thread
budget) builds a mixed-method two-engine fleet, pre-warms both shape
buckets, then drives a merge/queue/preempt-heavy load — and must record
ZERO post-warm compiles per engine. This is the regression gate for the
"N engines compiling inside each other's decode window" collapse.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.launch import host as hostmod

# ----------------------------------------------------------- unit half


def test_budget_derivation_partitions_cores():
    b = hostmod.compute_host_budget(4, cores=16)
    assert (b.engines, b.cores, b.intra_op, b.source) == \
        (4, 16, 4, "derived")
    assert "4 intra-op" in b.describe()


def test_budget_floors_at_one_thread():
    assert hostmod.compute_host_budget(8, cores=2).intra_op == 1
    assert hostmod.compute_host_budget(1, cores=0 or 1).intra_op == 1


def test_budget_override_wins():
    b = hostmod.compute_host_budget(4, threads_per_engine=3, cores=16)
    assert (b.intra_op, b.source) == (3, "override")


def test_budget_env_composes_without_mutating_process():
    before = dict(os.environ)
    b = hostmod.compute_host_budget(2, cores=2)     # -> 1 thread/engine
    env = hostmod.budget_env(b, host_devices=8, platform="cpu",
                             base={})
    assert env["PJRT_NPROC"] == "1"
    assert "--xla_cpu_multi_thread_eigen=false" in env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert dict(os.environ) == before               # pure composition


def test_budget_env_respects_existing_flags():
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_PLATFORMS": "tpu"}
    env = hostmod.budget_env(hostmod.compute_host_budget(1, cores=8),
                             host_devices=8, platform="cpu", base=base)
    # never override a caller's explicit choices
    assert env["XLA_FLAGS"].count(
        "--xla_force_host_platform_device_count") == 1
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["PJRT_NPROC"] == "8"


def test_apply_host_budget_refuses_live_backend():
    import jax
    jax.devices()                                   # force backend init
    with pytest.raises(RuntimeError, match="before the first jax"):
        hostmod.apply_host_budget(hostmod.compute_host_budget(1))


# ------------------------------------------------------- watchdog half

_REPORT = {}


def _report(tmp_path_factory):
    if not _REPORT:
        cache = str(tmp_path_factory.mktemp("compile_cache"))
        env = hostmod.budget_env(
            hostmod.compute_host_budget(2), host_devices=8,
            platform="cpu")
        env["PYTHONPATH"] = "src"
        r = subprocess.run(
            [sys.executable, os.path.join("tests", "_prewarm_child.py"),
             cache],
            capture_output=True, text=True, timeout=560, env=env, cwd=".")
        assert r.returncode == 0, r.stdout + r.stderr
        _REPORT.update(json.loads(r.stdout.strip().splitlines()[-1]))
        _REPORT["cache_entries"] = len(os.listdir(cache))
    return _REPORT


def test_zero_post_warm_compiles_under_mixed_load(tmp_path_factory):
    rep = _report(tmp_path_factory)
    assert rep["n_devices"] == 8
    assert {e["method"] for e in rep["per_engine"]} == \
        {"streaming", "fast"}
    for e in rep["per_engine"]:
        assert e["requests"] == 11
        assert e["prewarm_variants"] > 0
        assert e["mixed_block_programs"] > 0      # rows at two blocks
        assert e["post_warm_compiles"] == 0, e    # the watchdog itself


def test_budget_and_cache_reach_the_engines(tmp_path_factory):
    rep = _report(tmp_path_factory)
    for e in rep["per_engine"]:
        assert e["host_threads"] == rep["pjrt_nproc"] >= 1
        assert e["compile_misses"] >= e["prewarm_variants"]
    assert rep["cache_entries"] > 0
