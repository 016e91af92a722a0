"""Find a cell's parts by name.

``BENCHMARK.json`` at the checkout root names each workload, its
configuration and its traffic mix.  Everything else is a file of its own,
found by that name:

    bench/configs/<config>.json     published sizes, cut, family, reference
    bench/families/<family>.py      the model family (default ``decoder``)
    bench/references/<module>.py    the configuration's plain reference
    bench/traffic/<traffic>.json    parameters of the one traffic generator
    bench/cells/<workload>.json     engine settings, offered load, replicas
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/peaks.json                chip peaks keyed by ``device_kind``

A later cell, mix, configuration, model family or metric is added as new
files and new entries; nothing here needs an edit.

A model family is a module that defines, for the configurations that name
it (``"family": "<module>"``):

    shape_of(config, rehearsal=False) -> shape   plain sizes; ``rehearsal``
        gives the same structure at widths the CPU serves in seconds.  The
        harness reads ``name``, ``vocab``, ``context``, ``d_model`` and
        ``init_std`` (the guide bias); the rest is the family's own.
    make_weights(shape, seed, device=None) -> weights   the program's
        parameter tree in the served dtype, from the seed alone
    program_config(shape, *, context) -> the program's model config
    step_work(shape, lanes) / prefill_work(shape, n, offset=0) -> work
        of one fused step (``lanes``: per live lane its cache length and
        each live slot's ancestor count) and of one prefill: ``flops``,
        ``rows``, and a ``<k>_flops`` / ``<k>_bytes`` pair for each kernel
        ``<k>`` it counts, which gets a ``<k>_roof_s``

and its configuration names a plain reference (``"reference"``) with
``logits_at(shape, weights, tokens, rows, precision)``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # bench/configs/<config>.json
    traffic: Dict[str, Any]       # bench/traffic/<traffic>.json
    settings: Dict[str, Any]      # bench/cells/<workload>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    run_seconds: int


def _reports(metric: Dict[str, Any], workload: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or workload in wl


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files."""
    bench = _load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {names})")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load_json(root / conf["file"])
    config.setdefault("name", conf["name"])
    bench_dir = root / "bench"
    traffic = _load_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    traffic.setdefault("name", entry["traffic"])
    settings = _load_json(bench_dir / "cells" / f"{workload}.json")
    replicas = settings.get("replicas")
    if replicas and int(replicas["n"]) != int(entry["chips"]):
        raise ValueError(f"{workload}: {replicas['n']} replicas on "
                         f"{entry['chips']} chips; a replica takes a chip")
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload)]
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, settings=settings, end_to_end=e2e,
                per_layer=per_layer, run_seconds=int(bench["run_seconds"]))


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT
                  ) -> Callable[[Any], Optional[float]]:
    """``bench/metrics/<name>.py``'s ``read(run)``: the metric's value, or
    None where the run holds nothing to read."""
    mod = _load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}")
    return mod.read


def family_module(config: Dict[str, Any], root: Path = ROOT):
    """The configuration's model family (``bench/families/<family>.py``)."""
    name = config.get("family", "decoder")
    return _load_module(root / "bench" / "families" / f"{name}.py",
                        f"bench_family_{name}")


def reference_module(config: Dict[str, Any], root: Path = ROOT):
    """The configuration's plain reference (``bench/references/<m>.py``)."""
    name = config["reference"]
    return _load_module(root / "bench" / "references" / f"{name}.py",
                        f"bench_reference_{name}")


def peaks_for(device_kind: str, root: Path = ROOT) -> Dict[str, Any]:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = _load_json(root / "bench" / "peaks.json")
    row = table["devices"].get(device_kind)
    if row is None:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({', '.join(table['devices'])})")
    return row
