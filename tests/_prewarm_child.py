"""Subprocess body for the recompile-watchdog test (test_prewarm.py).

Runs under the exact process discipline ``launch/serve.py`` uses: host
budget env applied by the PARENT (before this interpreter existed),
persistent compile cache enabled, every engine pre-warmed for every
shape bucket the workload will hit — then a mixed-method, multi-bucket,
merge-and-preempt-heavy load. The contract under test: the measurement
window contains ZERO compiles (``post_warm_compiles == 0`` per engine).

Prints one JSON report as the last stdout line.
"""
import json
import sys

import numpy as np

from repro.launch import host as host_budgeting

CACHE_DIR = sys.argv[1]
host_budgeting.enable_compile_cache(CACHE_DIR)

import jax  # noqa: E402  (cache config must precede first compile)

from repro.core.decoder import DecodeConfig  # noqa: E402
from repro.launch.mesh import make_submeshes  # noqa: E402
from repro.models import get_config, init_params  # noqa: E402
from repro.serving import ContinuousEngine, DecodeExecutor  # noqa: E402

CFG = get_config("tiny")
PARAMS = init_params(CFG, jax.random.PRNGKey(3))
# two shape buckets; prompt_len is the EXACT tokenized length (shape
# buckets don't round — a one-byte miss is a fresh prefill variant,
# which is precisely what this watchdog exists to catch)
SHORT = [f"Q:{i}7+{i}1=? A:" for i in range(8)]
LONG = [f"Q:{i}70+{i}10=??? A:" for i in range(8)]
BUCKETS = [(len(SHORT[0]), 16), (len(LONG[0]), 8)]


def drive(eng):
    """Mixed-bucket load exercising every post-admission code path that
    could compile: queueing beyond max_slots, straggler merges (across
    block indexes too), and a preempt/park/resume cycle."""
    uids, comps = [], []
    uids.append(eng.submit(SHORT[0], max_tokens=16))
    comps += eng.step()                     # a row one block ahead ...
    for i in (1, 2):
        uids.append(eng.submit(SHORT[i], max_tokens=16))
    comps += eng.step()                     # ... merges with these
    for i in range(3):                      # staggered: forces ragged
        uids.append(eng.submit(LONG[i], max_tokens=8))
    comps += eng.step()                     # gangs form, stragglers next
    for i in range(3, 8):
        uids.append(eng.submit(SHORT[i], max_tokens=16))
    comps += eng.step()
    eng.preempt(uids[-1])                   # park + resume path
    comps += eng.run_to_completion()
    return uids, comps


def main():
    budget = host_budgeting.compute_host_budget(2)
    meshes = make_submeshes(2, 1, 1)
    methods = ("streaming", "fast")         # mixed-method fleet
    engines = [
        ContinuousEngine(
            CFG, PARAMS,
            DecodeConfig(method=m, gen_len=16, block_size=8, window=16),
            max_slots=4, executor=DecodeExecutor(CFG, PARAMS, mesh),
            host_budget=budget)
        for m, mesh in zip(methods, meshes)]
    warm = [e.prewarm(BUCKETS) for e in engines]

    per_engine = []
    for m, eng in zip(methods, engines):
        uids, comps = drive(eng)
        assert len(comps) == len(uids), (m, len(comps), len(uids))
        watch = eng.scheduler.compile_watch
        per_engine.append({
            "method": m,
            "requests": len(comps),
            "prewarm_variants": warm[len(per_engine)]["variants"],
            "compile_misses": watch.misses,
            "post_warm_compiles": watch.post_warm,
            "mixed_block_programs": eng.scheduler.mixed_block_programs,
            "host_threads": eng.metrics.host_threads,
        })

    print(json.dumps({
        "n_devices": len(jax.devices()),
        "pjrt_nproc": budget.intra_op,
        "per_engine": per_engine,
    }))


if __name__ == "__main__":
    main()
