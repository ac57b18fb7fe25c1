"""Find a cell's configuration, traffic mix, entry and metric readers by name.

Everything that belongs to one configuration, one traffic mix, one entry
or one per-layer metric lives in a file of its own, found from the names
in ``BENCHMARK.json`` and the configuration file:

* a configuration: the ``file`` its ``configs`` entry names (JSON);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* an entry: ``bench/entries/<layout>.py``, ``layout`` being the
  configuration's, a module with ``class Entry``:

  - ``Entry(cell, ref, devs)``: ``devs`` are the cell's ``chips``
    devices; puts the resident reference where the layout needs it;
  - ``dispatch(query)``: the program's device result, not waited for;
  - ``fetch(result)``: ``(best_start, best_dist, rounds, lb_pruned)`` as
    Python numbers;
  - dropping the entry (``del entry``) frees the program's state;

* a metric: ``bench/metrics/<name>.py``, a module with
  ``read(run) -> float | None`` (``None``: nothing to read in this run).

Adding a cell, a mix, a layout or a metric therefore adds files and
entries and edits none. ``root`` is the checkout's root directory.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    workload: dict     # the ``workloads`` entry
    config: dict       # the configuration file's contents
    traffic: dict      # the traffic file's contents
    end_to_end: list   # ``end_to_end`` entries this cell reports
    per_layer: list    # ``per_layer`` entries this cell reports


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_path(root: Path, name: str) -> Path:
    return Path(root) / "bench" / "traffic" / f"{name}.json"


def metric_path(root: Path, name: str) -> Path:
    return Path(root) / "bench" / "metrics" / f"{name}.py"


def entry_path(root: Path, layout: str) -> Path:
    return Path(root) / "bench" / "entries" / f"{layout}.py"


def _module(path: Path, name: str):
    """The module in file ``path``, loaded anew under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic files read."""
    root = Path(root)
    bm = load_benchmark(root)
    by_name = {w["name"]: w for w in bm["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    wl = by_name[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = json.loads((root / configs[wl["config"]]["file"]).read_text())
    traffic = json.loads(traffic_path(root, wl["traffic"]).read_text())
    return Cell(
        name=name, chips=int(wl["chips"]), workload=wl, config=config,
        traffic=traffic,
        end_to_end=[m for m in bm["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bm["per_layer"] if _reports(m, name)],
    )


def metric_module(name: str, root: Path = ROOT):
    """The reader module of metric ``name``."""
    return _module(metric_path(root, name), f"bench_metric_{name}")


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run) -> float | None`` function of metric ``name``."""
    return metric_module(name, root).read


def entry_class(layout: str, root: Path = ROOT):
    """The ``Entry`` class of ``layout``; raises ``FileNotFoundError``
    naming the path looked for where it has no file."""
    path = entry_path(root, layout)
    if not path.is_file():
        raise FileNotFoundError(f"no entry for layout {layout!r}: {path} "
                                "does not exist")
    return _module(path, f"bench_entry_{layout}").Entry
