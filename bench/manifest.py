"""Find a cell's configuration, traffic mix, load client and metrics by name.

``BENCHMARK.json`` at the checkout's root lists the cells.  Everything that
belongs to one configuration, one traffic mix or one metric is a file of
its own, found by its name:

* a configuration: the ``file`` its entry names (``bench/configs/``);
* a traffic mix: ``bench/traffic/<traffic>.json``, whose ``client`` key
  names the loop in ``bench/clients/<client>.py`` that reads it;
* a metric: ``bench/metrics/<name>.py``, whose ``read(record)`` returns
  the metric's value or ``None`` where the run holds nothing to read.  A
  quantity split by the end-to-end metric it moves (``dispatch_ms.stream``,
  ``dispatch_ms.closed``) shares one reader, ``bench/metrics/<quantity>.py``,
  named by the part before the first dot.

So a later cell or metric is new files and new entries, and no edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

__all__ = ["BENCH", "ROOT", "Cell", "load", "load_module", "reader"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    """Import the Python file at ``path`` under a name of its own."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # ... and with --trace 1

    def client(self):
        return load_module(BENCH / "clients" / f"{self.traffic['client']}.py")

    def metrics(self, traced: bool) -> dict:
        """``{name: (entry, read)}`` of the metrics this run reports."""
        entries = self.per_layer if traced else self.end_to_end
        return {m["name"]: (m, load_module(reader(m["name"])).read)
                for m in entries}


def reader(metric: str) -> Path:
    """The file that reads ``metric``: its own, else its quantity's."""
    own = BENCH / "metrics" / f"{metric}.py"
    return own if own.is_file() else (
        BENCH / "metrics" / f"{metric.split('.', 1)[0]}.py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, manifest: Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``workload`` of the manifest, with its files read."""
    spec = json.loads(Path(manifest).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"]
                         if _reports(m, workload)),
        per_layer=tuple(m for m in spec["per_layer"]
                        if _reports(m, workload)),
    )
