"""Host resource budgeting for multi-engine serving — the ONE
sanctioned place that mutates XLA/JAX process environment.

Running N ``EngineLoop`` decode threads in one process gives XLA:CPU a
single shared intra-op thread pool sized to every visible core; under
concurrent per-engine dispatch (and worse, concurrent first-block
compiles) the engines fight over it and per-engine decode-busy inflates
far beyond the work actually done (PR 6 trace attribution; ROADMAP open
item 1). The fix is to *budget*: size the pool to one engine's share of
the host, derived as ``cores // engines`` and overridable with
``--host-threads-per-engine``.

Mechanics:

* ``PJRT_NPROC`` — read by XLA's ``DefaultThreadPoolSize()`` when the
  CPU PjRt client is created; sizes the Eigen intra-op pool and the
  client's async work pool. This is the effective intra-op knob (XLA's
  flag parser has no intra-op thread-count flag).
* ``--xla_cpu_multi_thread_eigen=false`` — appended when the budget is
  a single thread, so legacy Eigen paths can't spawn their own workers.
* inter-op parallelism needs no flag here: the N decode threads *are*
  the inter-op dimension (one in-flight dispatch per engine by
  construction).

Every helper below must run **before the first jax backend
initialization** (env is read once at CPU client creation);
``apply_host_budget`` raises if a backend already exists. Nothing in
this module imports jax at module scope, so importing it is always
safe. ``scripts/test.sh lint`` enforces that no other module mutates
XLA-related environment — thread budgets, fake device counts, and the
persistent compile cache all flow through this file.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Dict, Optional

_XLA_ENV_KEYS = ("XLA_FLAGS", "PJRT_NPROC", "JAX_PLATFORMS")


@dataclasses.dataclass(frozen=True)
class HostBudget:
    """Effective per-engine host compute budget. ``intra_op`` is the
    XLA:CPU pool size each engine's dispatches may use; it is surfaced
    in ``/metrics`` (``repro_host_threads_per_engine``) and the engine
    span metadata so a trace always records what it ran under."""
    engines: int
    cores: int
    intra_op: int
    source: str          # "derived" | "override" | "pool/<either>"

    def describe(self) -> str:
        return (f"{self.intra_op} intra-op thread(s)/engine "
                f"({self.source}; {self.engines} engine(s) on "
                f"{self.cores} core(s))")


def compute_host_budget(engines: int, threads_per_engine: int = 0,
                        cores: Optional[int] = None) -> HostBudget:
    """Partition host compute across engines: ``cores // engines``
    intra-op threads each (floor 1), unless ``threads_per_engine``
    overrides it."""
    engines = max(1, engines)
    if cores is None:
        cores = os.cpu_count() or 1
    if threads_per_engine > 0:
        return HostBudget(engines, cores, threads_per_engine, "override")
    return HostBudget(engines, cores, max(1, cores // engines), "derived")


def compute_pool_budgets(pool_sizes: Dict[str, int],
                         threads_per_engine: int = 0,
                         cores: Optional[int] = None) \
        -> Dict[str, HostBudget]:
    """Per-pool budget records for a disaggregated fleet
    (``--pool prefill:N,decode:M``). ``PJRT_NPROC`` is process-global —
    every engine in the process shares ONE intra-op pool size, so the
    thread count is derived from the *total* engine count and cannot
    differ between pools; what differs per pool is the record itself
    (engine count, ``source="pool/..."``), which each engine carries
    into its metrics (``repro_host_threads_per_engine``) and trace
    spans so a post-mortem can see what a pool ran under. Apply the
    process env with ``apply_host_budget`` on the *total* budget."""
    total = sum(max(0, n) for n in pool_sizes.values())
    base = compute_host_budget(total, threads_per_engine, cores)
    return {role: HostBudget(n, base.cores, base.intra_op,
                             f"pool/{base.source}")
            for role, n in pool_sizes.items()}


def _backend_initialized() -> bool:
    mod = sys.modules.get("jax._src.xla_bridge")
    return bool(getattr(mod, "_backends", None))


def apply_host_budget(budget: HostBudget) -> HostBudget:
    """Apply ``budget`` to this process's environment. Must run before
    the first jax backend init — the CPU client reads ``PJRT_NPROC``
    exactly once at creation."""
    if _backend_initialized():
        raise RuntimeError(
            "apply_host_budget must run before the first jax backend "
            "initialization (XLA reads PJRT_NPROC once, at CPU client "
            "creation)")
    os.environ["PJRT_NPROC"] = str(budget.intra_op)
    if budget.intra_op == 1:
        _append_xla_flags("--xla_cpu_multi_thread_eigen=false")
    return budget


def force_host_device_count(n: int) -> None:
    """Fake ``n`` host devices (CI / demo meshes on CPU)."""
    _append_xla_flags(f"--xla_force_host_platform_device_count={n}")


def default_platform(platform: str = "cpu") -> None:
    """Pin the jax platform unless the caller already chose one."""
    os.environ.setdefault("JAX_PLATFORMS", platform)


def budget_env(budget: Optional[HostBudget] = None, *,
               host_devices: int = 0, platform: str = "",
               base: Optional[dict] = None) -> dict:
    """Environment dict for a *subprocess* (benchmark children, test
    harnesses): the same knobs ``apply_host_budget`` et al. set in this
    process, composed without mutating it."""
    env = dict(base if base is not None else os.environ)
    flags = env.get("XLA_FLAGS", "")
    if budget is not None:
        env["PJRT_NPROC"] = str(budget.intra_op)
        if budget.intra_op == 1 \
                and "--xla_cpu_multi_thread_eigen" not in flags:
            flags = (flags + " --xla_cpu_multi_thread_eigen=false").strip()
    if host_devices and "--xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count="
                 f"{host_devices}").strip()
    if flags:
        env["XLA_FLAGS"] = flags
    if platform:
        env.setdefault("JAX_PLATFORMS", platform)
    return env


def enable_compile_cache(cache_dir: str) -> None:
    """Wire JAX's persistent compilation cache at ``cache_dir`` and
    start counting its hit/miss events (``repro.obs.compile``). Safe to
    call after jax import (it uses ``jax.config``, not env). Raises on
    any failure: a server that silently runs without the cache pays
    every compile again on each start."""
    if not cache_dir:
        raise ValueError("enable_compile_cache needs a directory")
    import jax
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything — the fused per-block fns are exactly the
    # small-but-hot compiles the default min-time threshold skips
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.obs.compile import watch_persistent_cache
    watch_persistent_cache()


def _append_xla_flags(flag: str) -> None:
    cur = os.environ.get("XLA_FLAGS", "")
    if flag not in cur:
        os.environ["XLA_FLAGS"] = (cur + " " + flag).strip()
