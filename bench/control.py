"""The precision control of a cell, on the chip: for each seed, one
process-internal run of the cell's served path at its own size and
load (a short window), then the check with the reference in bfloat16
in the program's place (``correct.check``). Prints per seed whether
the control came out correct under the cell's limits (it must not),
its readings and the program's: the two readings a limit is set
between (``PERF.md``). The benchmark's own runs never run this.

    python bench/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import cells, harness
    from repro.obs.log import setup_logging
    cell = cells.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 1
    setup_logging(level="warning")
    for seed in args.seeds:
        res = harness.run(cell, seed, args.seconds, False,
                          time.perf_counter(), devices, control=True)
        print(json.dumps({"seed": seed, "control_correct": res["correct"],
                          "control": {k: v["value"] for k, v in
                                      res["checks"].items()},
                          "program": res["program_readings"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
