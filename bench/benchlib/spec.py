"""Find every piece of a cell by its name in ``BENCHMARK.json``.

* configuration ``<c>``  -> ``bench/configs/<c>.json`` (its ``reference``
  key names ``bench/references/<r>.py``)
* architecture           -> ``bench/arch/<model_type>.py``, by the
  configuration's ``model_type`` (``model_config``, ``check_config``,
  ``shapes``, ``EXPERT_LEAF``, ``decode_flops``, ``prefill_flops``,
  ``KERNELS``, ``calls_per_round``)
* traffic mix ``<t>``    -> ``bench/traffic/<t>.json``
* per-layer metric ``<m>`` -> ``bench/metrics/<m>.py`` (``read(rec)``)
* kernel roofline ``<k>`` -> ``bench/roofline/<k>.py`` (``match(op, m)``,
  ``cost(m, live, n_slots)``), for each ``<k>`` of the architecture's
  ``KERNELS``
* peaks                  -> ``bench/peaks.json``, keyed by ``device_kind``

A cell, configuration, architecture, mix or metric is added with new files
and entries only: nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from benchlib import BENCH_DIR, ROOT


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(name: str, root: Path = ROOT) -> dict:
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, bench: Path = BENCH_DIR) -> dict:
    return json.loads((bench / "configs" / f"{name}.json").read_text())


def traffic(name: str, bench: Path = BENCH_DIR) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(m: dict, bench: Path = BENCH_DIR):
    return load_module(bench / "references" / f"{m['reference']}.py")


def arch(m: dict, bench: Path = BENCH_DIR):
    """The module of configuration ``m``'s architecture, by its
    ``model_type``. There is no default: a type with no module fails."""
    path = bench / "arch" / f"{m['model_type']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"{m['name']}: no module for model_type {m['model_type']!r}: "
            f"{path.relative_to(bench.parent)} does not exist")
    return load_module(path)


def metric_reader(name: str, bench: Path = BENCH_DIR):
    return load_module(bench / "metrics" / f"{name}.py").read


def roofline(kernel: str, bench: Path = BENCH_DIR):
    return load_module(bench / "roofline" / f"{kernel}.py")


def peaks(kind: str, bench: Path = BENCH_DIR) -> dict:
    table = json.loads((bench / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def metrics_of(cell: str, kind: str, root: Path = ROOT) -> list:
    """The cell's metrics of ``kind`` (``end_to_end`` / ``per_layer``):
    those with no ``workloads`` list and those that list the cell."""
    return [x for x in benchmark(root)[kind]
            if cell in x.get("workloads", [cell])]
