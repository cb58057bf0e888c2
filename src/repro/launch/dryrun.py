import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, proving the distribution config is coherent,
and extract the roofline terms from the compiled artifact.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
        --shape decode_32k [--multi-pod] [--variant streaming|baseline]
    PYTHONPATH=src python -m repro.launch.dryrun --all

Results land in results/dryrun/<arch>__<shape>__<mesh>[__<variant>].json:
per-device memory (arguments/temp/output), per-device HLO FLOPs & bytes,
collective bytes by op type, and the derived roofline terms
(TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI).
"""
import argparse
import json
import re
import time
import traceback

import numpy as np

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8}

_COLL_RE = re.compile(
    r"(\w[\w.-]*)\s*=\s*(?:\()?((?:f|bf|s|u|pred|c)[\w]*)\[([\d,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


_CONVERT_RE = re.compile(r"= f32\[([\d,]+)\][^=]*\bconvert\(")


def parse_cpu_promotion_bytes(hlo_text: str, threshold=64 * 2**20) -> int:
    """Bytes of large f32 `convert` results. XLA:CPU has no native bf16
    arithmetic, so it converts bf16 buffers (params, KV caches) to f32 —
    and hoists whole-stack converts out of the layer scan. TPU consumes
    bf16 natively in the MXU, so these buffers don't exist there; we
    subtract them to get the TPU temp estimate (see §Dry-run notes)."""
    total = 0
    for m in _CONVERT_RE.finditer(hlo_text):
        size = 4
        for d in m.group(1).split(","):
            size *= int(d)
        if size >= threshold:
            total += size
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in compiled HLO."""
    out = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        _, dt, dims, kind = m.groups()
        if line.strip().startswith("%") and "-done" in line:
            continue
        size = 1
        if dims:
            for d in dims.split(","):
                size *= int(d)
        nbytes = size * _DTYPE_BYTES.get(dt, 4)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    return out


def roofline_terms(flops, hbm_bytes, coll: dict, n_chips: int) -> dict:
    """Per-device seconds for each roofline term. cost_analysis FLOPs
    are already per-device on SPMD-partitioned modules."""
    coll_bytes = sum(v["bytes"] for v in coll.values())
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": hbm_bytes / HBM_BW,
        "collective_s": coll_bytes / ICI_BW,
        "collective_bytes": coll_bytes,
    }


def model_flops(cfg, meta) -> float:
    """6*N*D (train) / 2*N*D (one forward) with N = active params."""
    n = cfg.active_param_count()
    if meta["kind"] == "train":
        tokens = meta["batch"] * meta["seq"]
        return 6.0 * n * tokens
    if meta["kind"] == "prefill":
        return 2.0 * n * meta["batch"] * meta["seq"]
    return 2.0 * n * meta["batch"] * meta["q_len"]


def SHAPE_KIND(shape_name: str) -> str:
    from repro.launch.steps import SHAPES
    return SHAPES[shape_name]["kind"]


def run_one(arch: str, shape_name: str, multi_pod: bool,
            variant: str = "streaming", out_dir: str = "results/dryrun",
            mesh_dims=None, unroll: int = 1):
    import jax
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh
    from repro.models import get_config

    t0 = time.perf_counter()
    kw = {}
    if mesh_dims:  # reduced-device test path only
        kw = dict(data=mesh_dims[0], model=mesh_dims[1])
    mesh = make_production_mesh(multi_pod=multi_pod, **kw)
    tp = mesh.shape["model"]
    cfg = get_config(arch, tp=tp, dtype="bfloat16", param_dtype="bfloat16",
                     block_size=steps.BLOCK,
                     # full unroll -> exact HLO flops/collective counts
                     # (XLA cost analysis counts a while body ONCE)
                     scan_unroll=(10_000 if unroll < 0 else unroll),
                     # activation checkpointing for the training pass
                     **({"remat": True} if SHAPE_KIND(shape_name) == "train"
                        else {}))
    spec = steps.build(cfg, mesh, shape_name, variant=variant)
    with mesh:
        lowered = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                          out_shardings=spec.out_shardings).lower(*spec.args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    promo = parse_cpu_promotion_bytes(hlo)
    n_chips = int(np.prod(list(mesh.shape.values())))
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    terms = roofline_terms(flops, hbm, coll, n_chips)
    mf = model_flops(cfg, spec.meta)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "variant": variant if spec.meta["kind"] == "decode" else "",
        "meta": spec.meta,
        "n_chips": n_chips,
        "per_device": {
            "flops": flops,
            "hbm_bytes": hbm,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "cpu_promotion_bytes": promo,
            "temp_bytes_tpu_estimate": max(mem.temp_size_in_bytes - promo, 0),
            "total_bytes": (mem.argument_size_in_bytes
                            + mem.output_size_in_bytes
                            + mem.temp_size_in_bytes),
            "total_bytes_tpu_estimate": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + max(mem.temp_size_in_bytes - promo, 0)),
        },
        "collectives": coll,
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops if flops else None,
        "unrolled": unroll != 1,
        "compile_s": round(time.perf_counter() - t0, 1),
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    rec["dominant_term"] = dom
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{rec['mesh']}"
    if rec["variant"] and rec["variant"] != "streaming":
        tag += f"__{variant}"
    if rec["unrolled"]:
        tag += "__unrolled"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"OK {tag}: mem/dev={rec['per_device']['total_bytes']/2**30:.2f}GiB "
          f"(tpu-est {rec['per_device']['total_bytes_tpu_estimate']/2**30:.2f}) "
          f"flops/dev={flops:.3g} dom={dom} "
          f"terms=({terms['compute_s']:.2e},{terms['memory_s']:.2e},"
          f"{terms['collective_s']:.2e})s compile={rec['compile_s']}s")
    return rec


def _compile_stats(cfg, mesh, shape_name, variant):
    """Lower+compile; return (flops, hbm_bytes, collectives, mem, hlo)."""
    import jax
    from repro.launch import steps
    spec = steps.build(cfg, mesh, shape_name, variant=variant)
    with mesh:
        compiled = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                           out_shardings=spec.out_shardings) \
            .lower(*spec.args).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            parse_collectives(hlo), mem, hlo, spec)


def run_corrected(arch: str, shape_name: str, variant: str = "streaming",
                  out_dir: str = "results/roofline", mesh_dims=None,
                  multi_pod: bool = False):
    """Exact-trip-count roofline record via finite differences.

    XLA cost analysis counts a `while` (scan) body ONCE regardless of
    trip count, and fully unrolling big training graphs is prohibitively
    slow to compile. Instead compile the same program with scan
    unroll=1 and unroll=2: the difference isolates one scan-body's
    flops/bytes/collectives, and

        total = u1 + (reps - 1) * (u2 - u1)

    recovers the true per-step totals (tail layers and out-of-loop ops
    live in u1). Validated against a full unroll on qwen3-32b
    decode_32k (see EXPERIMENTS.md §Roofline notes).
    """
    import dataclasses as _dc
    import jax
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh
    from repro.models import get_config

    t0 = time.perf_counter()
    os.environ["REPRO_DISABLE_CHUNKING"] = "1"
    kw = dict(data=mesh_dims[0], model=mesh_dims[1]) if mesh_dims else {}
    mesh = make_production_mesh(multi_pod=multi_pod, **kw)
    base = get_config(arch, tp=mesh.shape["model"], dtype="bfloat16",
                      param_dtype="bfloat16", block_size=steps.BLOCK,
                      **({"remat": True} if SHAPE_KIND(shape_name) == "train"
                         else {}))
    cfg1 = _dc.replace(base, scan_unroll=1)
    cfg2 = _dc.replace(base, scan_unroll=2)
    f1, b1, c1, mem1, hlo1, spec = _compile_stats(cfg1, mesh, shape_name,
                                                  variant)
    f2, b2, c2, *_ = _compile_stats(cfg2, mesh, shape_name, variant)
    R = base.reps
    flops = f1 + (R - 1) * (f2 - f1)
    hbm = b1 + (R - 1) * (b2 - b1)
    coll = {}
    for kind in set(c1) | set(c2):
        a = c1.get(kind, {"count": 0, "bytes": 0})
        b = c2.get(kind, {"count": 0, "bytes": 0})
        coll[kind] = {
            "count": a["count"] + (R - 1) * (b["count"] - a["count"]),
            "bytes": a["bytes"] + (R - 1) * (b["bytes"] - a["bytes"]),
        }
    promo = parse_cpu_promotion_bytes(hlo1)
    n_chips = int(np.prod(list(mesh.shape.values())))
    terms = roofline_terms(flops, hbm, coll, n_chips)
    mf = model_flops(base, spec.meta)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "variant": variant if spec.meta["kind"] == "decode" else "",
        "meta": spec.meta, "n_chips": n_chips,
        "per_device": {
            "flops": flops, "hbm_bytes": hbm,
            "argument_bytes": mem1.argument_size_in_bytes,
            "output_bytes": mem1.output_size_in_bytes,
            "temp_bytes": mem1.temp_size_in_bytes,
            "cpu_promotion_bytes": promo,
            "temp_bytes_tpu_estimate": max(mem1.temp_size_in_bytes - promo, 0),
            "total_bytes": (mem1.argument_size_in_bytes
                            + mem1.output_size_in_bytes
                            + mem1.temp_size_in_bytes),
            "total_bytes_tpu_estimate": (
                mem1.argument_size_in_bytes + mem1.output_size_in_bytes
                + max(mem1.temp_size_in_bytes - promo, 0)),
        },
        "collectives": coll,
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops if flops else None,
        "unrolled": True, "method": "trip_count_diff",
        "compile_s": round(time.perf_counter() - t0, 1),
    }
    rec["dominant_term"] = max(("compute_s", "memory_s", "collective_s"),
                               key=lambda k: terms[k])
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{rec['mesh']}"
    if rec["variant"] and rec["variant"] != "streaming":
        tag += f"__{variant}"
    tag += "__unrolled"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    t = terms
    print(f"OK {tag}: flops/dev={flops:.3g} dom={rec['dominant_term']} "
          f"terms=({t['compute_s']:.2e},{t['memory_s']:.2e},"
          f"{t['collective_s']:.2e})s compile={rec['compile_s']}s",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="streaming")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--mesh-dims", default="",
                    help="testing only: 'data,model' override")
    ap.add_argument("--unroll", type=int, default=1,
                    help="scan unroll; -1 = full (exact flops accounting)")
    args = ap.parse_args()
    mesh_dims = tuple(int(x) for x in args.mesh_dims.split(",")) \
        if args.mesh_dims else None

    if args.all:
        from repro.configs import ASSIGNED
        from repro.launch.steps import SHAPES
        failures = []
        for arch in ASSIGNED:
            for shape in SHAPES:
                for mp in (False, True):
                    try:
                        run_one(arch, shape, mp, out_dir=args.out,
                                unroll=args.unroll)
                    except Exception as e:
                        failures.append((arch, shape, mp, repr(e)))
                        print(f"FAIL {arch} {shape} mp={mp}: {e}")
                        traceback.print_exc()
        print(f"{len(failures)} failures")
        raise SystemExit(1 if failures else 0)
    run_one(args.arch, args.shape, args.multi_pod, args.variant, args.out,
            mesh_dims=mesh_dims, unroll=args.unroll)


if __name__ == "__main__":
    main()
