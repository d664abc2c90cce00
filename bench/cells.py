"""Find a cell's pieces by name: nothing here lists configurations, mixes
or metrics. ``BENCHMARK.json`` names them, and each lives in a file of its
own under the benchmark's directory:

* configuration — the ``file`` its ``configs`` entry gives;
* traffic mix   — ``traffic/<traffic>.json``;
* driver        — ``drivers/<kind>.py`` for the mix's ``kind``;
* metric        — ``metrics/<name>.py``, whose ``read(run)`` returns the
  number or None where the run holds nothing to read; a metric split by
  cells (``decode_step_ms.chat``) falls back to the reader of its base
  name (``metrics/decode_step_ms.py``) where it has none of its own;
* limits        — ``limits/<workload>.json``, the numbers ``correct``
  compares against.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple        # metric entries this cell reports, trace off
    per_layer: tuple         # ... and with --trace 1
    bench: Path


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name`` in ``<root>/BENCHMARK.json``."""
    bench_json = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / bench_json["paths"][0]
    cells = {w["name"]: w for w in bench_json["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench_json["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits_file = bench / "limits" / f"{name}.json"
    limits = (json.loads(limits_file.read_text())
              if limits_file.exists() else {})
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                limits=limits,
                end_to_end=tuple(m for m in bench_json["end_to_end"]
                                 if _reports(m, name)),
                per_layer=tuple(m for m in bench_json["per_layer"]
                                if _reports(m, name)),
                bench=bench)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    kind = cell.traffic["kind"]
    return _module(cell.bench / "drivers" / f"{kind}.py",
                   f"bench_driver_{kind}")


def reader(bench: Path, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``, else of the file
    for the name without its last ``.<suffix>``."""
    path = bench / "metrics" / f"{metric}.py"
    if not path.exists() and "." in metric:
        path = bench / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    mod = _module(path, "bench_metric_" + path.stem.replace(".", "_")
                  .replace("-", "_"))
    return mod.read
