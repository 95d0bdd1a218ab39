"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, named after it:

  configs/<config>.json     the model as run (plus its coding point);
                            its ``arch`` key names its module
  archs/<arch>.py           the model's weights, reference layers and
                            work counts (``arch_module``)
  traffic/<traffic>.json    parameters of the one traffic generator
  arrivals/<process>.py     an arrival process a mix names
  cells/<workload>.json     pool size, offered rate and the limits of
                            the correctness comparison of one cell
  metrics/<metric>.py       the reader of one per-layer metric

so a later change adds a model, a configuration, a mix or a metric by
adding a file and an entry, never by editing one that is there.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
import types
from pathlib import Path
from typing import Callable, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str]          # per-layer metrics only
    layer: Optional[str]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict                  # configs/<config>.json
    arch: types.ModuleType        # archs/<config["arch"]>.py
    traffic: dict                 # traffic/<traffic>.json
    cell: dict                    # cells/<workload>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reported(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def _metric(entry: dict) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"],
                  better=entry["better"], source=entry["source"],
                  moves=entry.get("moves"), layer=entry.get("layer"))


def load_cell(workload: str, bench: Optional[dict] = None,
              root: Path = ROOT, here: Path = HERE) -> Cell:
    """The cell named ``workload`` with its configuration, traffic mix,
    cell file and the metrics it reports."""
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    path = root / configs[w["config"]]["file"]
    config = load_json(path)
    if "arch" not in config:
        raise KeyError(f"configuration {w['config']!r} ({path}) has no "
                       f"'arch' key: it names archs/<arch>.py, the module "
                       f"that holds its model")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        arch=arch_module(config["arch"], here / "archs"),
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
        cell=load_json(here / "cells" / f"{workload}.json"),
        end_to_end=[_metric(m) for m in bench["end_to_end"]
                    if _reported(m, workload)],
        per_layer=[_metric(m) for m in bench["per_layer"]
                   if _reported(m, workload)])


@functools.lru_cache(maxsize=None)
def load_module(path: Path, what: str):
    """The Python file at ``path``, loaded as a module of its own, once
    per process, and registered under its own name (as an import would,
    so that a dataclass in it can find its module)."""
    if not path.is_file():
        raise FileNotFoundError(f"{what} has no module at {path}")
    name = "chipbench_" + "".join(c if c.isalnum() else "_"
                                  for c in f"{path.parent.name}_{path.stem}")
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    sys.modules[name] = module
    module_spec.loader.exec_module(module)
    return module


def metric_reader(name: str, directory: Path = HERE / "metrics"
                  ) -> Callable:
    """``read(ctx) -> float | None`` from ``metrics/<name>.py``."""
    return load_module(directory / f"{name}.py",
                       f"per-layer metric {name!r}").read


def arch_module(name: str, directory: Path = HERE / "archs"
                ) -> types.ModuleType:
    """The architecture module ``archs/<name>.py``: everything that knows
    a model's layout, so that the shared files know none.  It provides

      Dims, dims(config)        the model's sizes from its configuration
                                file; shared code reads only ``layers``
                                (those with a KV cache), ``hidden``,
                                ``heads``, ``kv_heads``, ``head_dim``,
                                ``vocab``, ``eps``, ``rope_theta``,
                                ``embed_mult`` and ``dtype``
      matmul_params(dims)       parameters a token meets in products
      program_model(config)     the program's ModelConfig
      reduced(config)           the file at the program's reduced size,
                                for CPU rehearsals
      init(dims, key)           the weight tree, in the program's layout
      hidden_states(dims, params, x, numerics)
                                the reference's layer stack and final
                                norm: (S, T, d) embeddings -> states
      first_kv(dims, params, x, numerics)
                                the reference's first attention cache
                                (keys, values) of the embeddings ``x``
      embedding(params), unembedding(params)
                                the (V, d) input and output tables
      program_first_kv(state, streams, positions)
                                where that cache lies in the program's
                                pool state
    """
    return load_module(directory / f"{name}.py",
                       f"architecture {name!r}")
