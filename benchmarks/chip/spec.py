"""Finds everything a run needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of its
own, so a new cell, configuration, traffic mix or metric is a new file plus
a new entry in ``BENCHMARK.json`` and never an edit of a file that exists:

  <bench>/configs/<config>.json   (the configuration's ``file`` entry)
  <bench>/traffic/<traffic>.json  the training job: batch, sequence, k,
                                  optimizer, document-length law, corpus
  <bench>/limits/<cell>.json      the limits of the numbers that decide
                                  ``correct``, with the readings behind them
  <bench>/metrics/<metric>.py     one reader per metric: ``read(run)``
  <bench>/families/<family>.py    everything that depends on the model's
                                  shape, for the configurations that name
                                  this ``"family"`` (``"dense"`` where a
                                  configuration names none)

``<bench>`` is the first of ``paths``; every file name is relative to the
root that holds ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    bench_dir: pathlib.Path


def _load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reader(bench_dir: pathlib.Path, name: str) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    mod_spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


HERE = pathlib.Path(__file__).resolve().parent
# the in-memory key under which ``load_cell`` records where the
# configuration's family module lies
FAMILY_FILE = "family_file"


def family(conf: Dict):
    """The family module of a configuration: the file ``load_cell`` found
    for it, else ``families/<family>.py`` beside this file."""
    path = conf.get(FAMILY_FILE) or HERE / "families" / f"{conf.get('family', 'dense')}.py"
    return load_family(str(path))


@functools.lru_cache(maxsize=None)
def load_family(path: str):
    path = pathlib.Path(path)
    if not path.exists():
        raise FileNotFoundError(f"model family {path.stem!r} has no module at {path}")
    mod_spec = importlib.util.spec_from_file_location(f"chipbench_family_{path.stem}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _metrics(entries, bench_dir) -> List[Metric]:
    """Every metric is read in every cell; a reader that finds nothing to
    read returns None and its metric is left out of the run's line."""
    return [Metric(e["name"], e["unit"], _reader(bench_dir, e["name"])) for e in entries]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    root = pathlib.Path(root)
    bench = _load_json(root / "BENCHMARK.json")
    bench_dir = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = _load_json(root / configs[w["config"]]["file"])
    conf[FAMILY_FILE] = str(bench_dir / "families" / f"{conf.get('family', 'dense')}.py")
    family(conf)
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(bench_dir / "limits" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=conf, traffic=traffic, limits=limits,
        end_to_end=_metrics(bench["end_to_end"], bench_dir),
        per_layer=_metrics(bench["per_layer"], bench_dir),
        bench_dir=bench_dir,
    )
