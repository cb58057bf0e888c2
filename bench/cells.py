"""Finds a cell's parts by name: ``BENCHMARK.json`` at the root of the
checkout, the configuration file it names, the model family that file's
``reference`` key names (``bench/families/<family>.py``),
``bench/traffic/<mix>.json`` and one reader per metric,
``bench/metrics/<metric>.py``. Adding a configuration, a family, a mix
or a metric is adding a file and an entry."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(Exception):
    """The cell or one of its files cannot be found or read."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict               # the configuration file's contents
    traffic: dict              # the traffic file's contents
    end_to_end: List[dict]     # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"cannot read {path}: {e}") from e


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name} names config {w['config']!r}, "
                        "which BENCHMARK.json does not list")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_reader(metric: str, bench_dir: str = BENCH_DIR) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    if not os.path.exists(path):
        raise CellError(f"no reader for metric {metric!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_overrides(config: dict) -> Dict[str, object]:
    """The program's ``ModelConfig`` fields, from the configuration's
    own keys through its ``program_keys`` map."""
    return {field: config[key]
            for key, field in config["program_keys"].items()}


def model_dims(config: dict) -> dict:
    """Sizes the FLOP and byte functions and the reference use, in one
    vocabulary whatever the source's key names: the method's here, the
    model's from the ``dims`` of the family that the configuration's
    ``reference`` key names (``bench/families/``), and ``family``, that
    name."""
    from bench import families      # which imports this module
    o = program_overrides(config)
    eng = config["engine"]
    name = config["reference"]
    return dict(families.load(name).dims(config), family=name,
                layers=o["n_layers"], block=o["block_size"],
                window=eng["window"], mask_id=o["mask_token_id"],
                eos_id=o["eos_token_id"],
                dtype_bytes={"float32": 4, "bfloat16": 2}[o["dtype"]],
                tau0=eng["tau0"], alpha=eng["alpha"])


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = _read_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]
