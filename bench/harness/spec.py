"""Find a cell's files by the names in ``BENCHMARK.json``.

The checkout root holds ``BENCHMARK.json``; everything else of the
benchmark sits under ``bench/``:

* ``configs/<config>.json``  the configuration as it is run; its keys
  name what belongs to it alone: ``reference`` (the plain reference
  beside it), ``weights`` (its weights from the seed, their layout in the
  program, and ``arch``, the model the engine must serve), ``counts``
  (its operations and bytes, read by the roofline and MFU metrics) and
  ``tiny`` (its CPU sizes, for the tests);
* ``traffic/<traffic>.json`` the traffic mix: its ``driver`` key names
  ``drivers/<driver>.py``, the rest are the driver's parameters;
* ``metrics/<metric>.py``    one reader per metric, ``read(run)``.
"""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list

    def replace(self, config=None, traffic=None) -> "Cell":
        """A copy with parts of the configuration or traffic overridden."""
        out = copy.deepcopy(self)
        _merge(out.config, config or {})
        _merge(out.traffic, traffic or {})
        return out


def _merge(base: dict, over: dict) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _merge(base[k], v)
        else:
            base[k] = v


def load_json(rel: str) -> dict:
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


def load_module(rel: str):
    """Import ``bench/<rel>`` by path (names may hold ``.`` and ``-``)."""
    path = os.path.join(BENCH, rel)
    name = "bench_" + rel.replace("/", "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a metric with no list is reported wherever what it moves is
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    try:
        entry = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=load_json(f"configs/{entry['config']}.json"),
        traffic=load_json(f"traffic/{entry['traffic']}.json"),
        end_to_end=e2e,
        per_layer=layer,
    )
