#!/usr/bin/env bash
# Tier-1 test entrypoint + serving smoke.
#
#   scripts/test.sh              # full pytest suite (tier-1 command)
#   scripts/test.sh smoke        # fast serving smoke: both engine modes
#   scripts/test.sh kernels      # kernel-parity + fused-loop tests and a
#                                # Pallas-routed continuous-serve smoke
#   scripts/test.sh server       # HTTP front-end tests (loopback round
#                                # trip, SSE, 429, deadlines, disconnect)
#   scripts/test.sh sharded      # mesh-parallel decode suite (forced
#                                # 8-device host mesh) + sharded bench
#   scripts/test.sh disagg       # disaggregated prefill/decode pool
#                                # suite (roles, radix-store handoff,
#                                # crash re-route) + mixed-workload
#                                # insulation bench
#   scripts/test.sh cache        # cross-request prefix cache suite +
#                                # a quick bench_cache run
#   scripts/test.sh obs          # observability suite (tracer, span
#                                # trees, telemetry, histograms, logs)
#   scripts/test.sh series       # time-series suite (metrics recorder,
#                                # /debug/timeline, /console, fleet
#                                # fan-in, Prometheus exposition)
#   scripts/test.sh audit        # quality-audit suite (shadow auditor,
#                                # fault injection, SLO watchdog,
#                                # flight recorder, /debug routes)
#   scripts/test.sh gate         # regenerate the quick benches and
#                                # gate them against the committed
#                                # baseline (scripts/bench_gate.py)
#   scripts/test.sh lint         # compileall + import-cycle smoke +
#                                # no-print policy + raise discipline
#                                # in observability hot paths + metrics
#                                # doc drift check (also runs at the
#                                # top of tier-1)
#   scripts/test.sh all          # suite + smoke
#
# Tests run on the single real CPU device; the dry-run subprocesses set
# their own XLA device-count flags (never export device-count flags
# globally here — see tests/conftest.py).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

run_lint() {
    # fail fast on syntax errors and package-level import cycles before
    # paying for any jit compile: byte-compile the whole tree, then
    # import every repro package fresh in one interpreter
    python -m compileall -q src
    python - <<'EOF'
import importlib, pkgutil
import repro
mods = [m.name for m in pkgutil.walk_packages(repro.__path__, "repro.")
        if ".launch." not in m.name       # launchers parse argv/XLA flags
        and not m.name.endswith("__main__")]
for name in sorted(mods):
    importlib.import_module(name)
print(f"lint: imported {len(mods)} repro modules, no cycles")
EOF
    python - <<'EOF'
# library code must log via repro.obs.log, not print: an embedded
# engine should never write to a server's stdout. AST-based (docstring
# examples showing print() are fine); the launch CLIs are the
# allowlisted user-facing surface.
import ast, pathlib, sys
bad = []
for path in sorted(pathlib.Path("src/repro").rglob("*.py")):
    if "launch" in path.parts:
        continue
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"):
            bad.append(f"{path}:{node.lineno}")
if bad:
    print("lint: bare print() in library code (use repro.obs.log):")
    print("\n".join(f"  {b}" for b in bad))
    sys.exit(1)
print("lint: no bare print() outside src/repro/launch")
EOF
    python - <<'EOF'
# XLA/JAX process environment is mutated in exactly one place:
# repro.launch (host budgets, fake device counts, platform pins, the
# persistent compile cache). Anywhere else, a write to XLA_FLAGS /
# PJRT_NPROC / JAX_PLATFORMS silently depends on import order and
# defeats the per-engine budget — so the lint walks every assignment,
# os.environ[...] store, setdefault, update, putenv, and pop for those
# keys. Benchmarks compose child env dicts via
# repro.launch.host.budget_env (pure, no process mutation) instead.
import ast, pathlib, sys
KEYS = ("XLA_FLAGS", "PJRT_NPROC", "JAX_PLATFORMS")

def names_env(node):        # os.environ or environ
    return (isinstance(node, ast.Attribute) and node.attr == "environ") \
        or (isinstance(node, ast.Name) and node.id == "environ")

def key_is_xla(node):
    return isinstance(node, ast.Constant) and node.value in KEYS

bad = []
roots = [pathlib.Path("src/repro"), pathlib.Path("benchmarks")]
for root in roots:
    for path in sorted(root.rglob("*.py")):
        if path.parts[:3] == ("src", "repro", "launch"):
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            hit = False
            # os.environ["XLA_FLAGS"] = ... (incl. augmented/annotated)
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                tgts = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                hit = any(isinstance(t, ast.Subscript) and names_env(t.value)
                          and key_is_xla(t.slice) for t in tgts)
            # os.environ.setdefault/update/pop("XLA_FLAGS", ...), putenv
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute):
                f = node.func
                if f.attr in ("setdefault", "pop", "update") \
                        and names_env(f.value):
                    hit = any(key_is_xla(a) for a in node.args) or any(
                        kw.arg in KEYS for kw in node.keywords)
                elif f.attr == "putenv":
                    hit = any(key_is_xla(a) for a in node.args)
            if hit:
                bad.append(f"{path}:{node.lineno}")
if bad:
    print("lint: XLA env mutated outside repro.launch "
          "(route through repro.launch.host):")
    print("\n".join(f"  {b}" for b in bad))
    sys.exit(1)
print("lint: XLA env (XLA_FLAGS/PJRT_NPROC/JAX_PLATFORMS) only "
      "mutated in repro.launch")
EOF
    python - <<'EOF'
# observability hot paths must log-and-drop, never raise: a tracer or
# auditor exception inside the decode thread would kill paying traffic
# to report on it. AST lint: no `raise` statement in the tracer/auditor
# modules outside the explicitly-allowlisted functions (request_tree is
# an offline analysis helper whose ValueError IS its contract;
# __post_init__ is config validation at construction time, before any
# hot path exists).
import ast, pathlib, sys
FILES = ("src/repro/obs/trace.py", "src/repro/obs/audit.py",
         "src/repro/obs/series.py")
ALLOWED = {"request_tree", "__post_init__"}
bad = []
for fname in FILES:
    tree = ast.parse(pathlib.Path(fname).read_text(), filename=fname)
    # map every node to its innermost enclosing function name
    def walk(node, fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn = node.name
        if isinstance(node, ast.Raise) and fn not in ALLOWED:
            bad.append(f"{fname}:{node.lineno} (in {fn or '<module>'})")
        for child in ast.iter_child_nodes(node):
            walk(child, fn)
    walk(tree, None)
if bad:
    print("lint: raise in an observability hot path (log-and-drop "
          "instead; allowlist: request_tree, __post_init__):")
    print("\n".join(f"  {b}" for b in bad))
    sys.exit(1)
print(f"lint: no raise outside {sorted(ALLOWED)} in "
      f"{len(FILES)} obs hot-path modules")
EOF
    # docs/METRICS.md must match a fresh /metrics rendering (every
    # repro_* literal in the server source covered and documented)
    python scripts/gen_metrics_doc.py --check
}

run_suite() {
    run_lint
    python -m pytest -x -q "$@"
}

run_cache() {
    # prefix-cache suite (radix store, cached-prefill identity,
    # routing), then the cache bench on the quick workload
    python -m pytest -x -q tests/test_cache.py
    echo "== bench_cache --quick =="
    python benchmarks/bench_cache.py --quick \
        --out results/BENCH_cache_quick.json
}

run_smoke() {
    # tiny end-to-end serve in both modes; --train-steps kept small so
    # the smoke stays fast (accuracy is not asserted here)
    for mode in continuous batch; do
        echo "== smoke: repro.launch.serve --mode $mode =="
        python -m repro.launch.serve --arch tiny --n 8 --mode "$mode" \
            --train-steps 120 --max-slots 4
    done
}

run_kernels() {
    # kernel-parity sweeps + fused-loop identity tests, then a fused
    # continuous-serve smoke with attention/confidence routed through
    # the Pallas kernels (interpret mode on CPU, compiled on TPU; the
    # TPU compiles at real widths are in tests/test_tpu_compile.py)
    python -m pytest -x -q tests/test_kernels.py tests/test_tpu_compile.py \
        tests/test_fused_decode.py
    echo "== smoke: repro.launch.serve --mode continuous --use-kernels =="
    python -m repro.launch.serve --arch tiny --n 4 --mode continuous \
        --train-steps 120 --max-slots 4 --use-kernels
}

run_obs() {
    # observability suite, then the tracer-overhead bench (asserts
    # tracer-on decode throughput within 5% and host_syncs_per_block
    # unchanged; the full run writes results/BENCH_obs.json)
    python -m pytest -x -q tests/test_obs.py
    echo "== bench_obs --quick =="
    python benchmarks/bench_obs.py --quick --out results/BENCH_obs_quick.json
}

run_series() {
    # time-series recorder suite: ring sampling + delta reconstruction,
    # fleet fan-in, /debug/timeline + /console round trips, strict
    # Prometheus-exposition parse of /metrics, writer-vs-reader
    # concurrency hammer
    python -m pytest -x -q tests/test_series.py
}

run_audit() {
    # quality-audit suite: shadow-auditor clean matrix + fault
    # injection (flipped token, poisoned cache chunk), SLO watchdog,
    # flight recorder, /debug/vars + /debug/flight
    python -m pytest -x -q tests/test_audit.py
}

run_gate() {
    # regenerate the quick benches into a scratch dir and gate them
    # against the committed results/ tree (git:HEAD): perf within
    # loose ratios, structural invariants (host_syncs_per_block, the
    # benches' own within_tolerance verdicts) exact
    local fresh="results/gate_fresh"
    mkdir -p "$fresh"
    python benchmarks/bench_obs.py --quick \
        --out "$fresh/BENCH_obs_quick.json"
    python benchmarks/bench_cache.py --quick \
        --out "$fresh/BENCH_cache_quick.json"
    python benchmarks/bench_disagg.py --quick \
        --out "$fresh/BENCH_disagg_quick.json"
    python scripts/bench_gate.py --fresh "$fresh" --baseline git:HEAD \
        --out results/GATE.json
    # the benches above each appended a history record; validate the
    # whole history tree against the record schema
    python scripts/perf_report.py --check
}

run_disagg() {
    # disaggregated prefill/decode pools: role-fenced stealing, the
    # prefill->decode handoff through the shared radix store (token
    # identity vs the co-located path), crash re-route, cancel races,
    # drain ordering; then the mixed-workload bench (steady decode
    # stream + Poisson long-prompt storm) comparing co-located vs
    # pooled fleets in budgeted subprocesses
    python -m pytest -x -q tests/test_disagg.py
    echo "== bench_disagg --quick =="
    python benchmarks/bench_disagg.py --quick \
        --out results/BENCH_disagg_quick.json
}

run_server() {
    # loopback HTTP/SSE tests; also part of the tier-1 suite (the file
    # lives in tests/, so the plain pytest run picks it up too)
    python -m pytest -x -q tests/test_server.py
}

run_sharded() {
    # mesh-parallel gang decode: the pytest files drive subprocesses
    # that force an 8-device host mesh (the flag must never be set in
    # the main pytest process — see tests/conftest.py). test_prewarm is
    # the recompile watchdog (zero post-warm compiles under mixed-
    # method multi-bucket load); test_steal is the work-stealing
    # identity/lifecycle suite. Then the sharded bench exercises
    # budgeted 1/2-engine routing over real sockets.
    python -m pytest -x -q tests/test_sharded_decode.py \
        tests/test_prewarm.py tests/test_steal.py
    echo "== bench_sharded --quick (8 forced host devices) =="
    # the bench composes each child's env via repro.launch.host
    # (budget_env) — don't clobber a developer's ambient XLA_FLAGS here
    python benchmarks/bench_sharded.py --quick \
        --out results/BENCH_sharded_quick.json
}

case "${1:-suite}" in
    smoke)   run_smoke ;;
    kernels) run_kernels ;;
    server)  run_server ;;
    sharded) run_sharded ;;
    disagg)  run_disagg ;;
    cache)   run_cache ;;
    obs)     run_obs ;;
    series)  run_series ;;
    audit)   run_audit ;;
    gate)    run_gate ;;
    lint)    run_lint ;;
    all)     run_suite; run_smoke ;;
    suite)   run_suite ;;
    *)       run_suite "$@" ;;
esac
