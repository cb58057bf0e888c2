"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (a model configuration under a traffic mix) is found by name
in ``BENCHMARK.json``. The run makes its weights and traffic from the
seed, sets up and warms the served path, drives it over loopback HTTP
for ``--seconds``, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics from a profiled window), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number with its
limit (also the last lines of standard error).

It needs a TPU: without one, or with fewer chips than the cell asks
for, it prints no result and exits non-zero.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cells
    try:
        cell = cells.load_cell(args.workload)
    except cells.CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    try:
        import jax
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: no accelerator: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    from bench import harness
    from repro.obs.log import setup_logging
    setup_logging(level="warning")
    print(f"device: {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}; cell {cell.name}, seed {args.seed}, "
          f"{args.seconds:g}s, trace {args.trace}", file=sys.stderr)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_PROC, devices)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
