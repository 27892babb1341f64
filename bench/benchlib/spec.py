"""Find a cell's files by the names in ``BENCHMARK.json``.

Every configuration, traffic mix, limit set and per-layer metric sits in a
file of its own, so a later cell is added with files and entries alone:

    bench/configs/<config>.json     the deployment as it is run
    bench/traffic/<traffic>.json    the request mix; its ``loop`` names a kind
    bench/loops/<kind>.py           the request loop of that kind, ``Loop``
    bench/limits/<workload>.json    the limit of each number ``correct`` compares
    bench/metrics/<metric>.py       a reader ``read(ctx) -> float | None``,
                                    for end-to-end and per-layer metrics alike
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list        # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, spec: dict | None = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    spec = load_spec(bench_dir.parent) if spec is None else spec
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]

    def read(kind, stem):
        return json.loads((bench_dir / kind / f"{stem}.json").read_text())

    return Cell(
        name=name, chips=int(w["chips"]), config=read("configs", w["config"]),
        traffic=read("traffic", w["traffic"]), limits=read("limits", name),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def _load(path: Path, mod_name: str):
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py`` (metric names
    hold dots, so the file is loaded by path, not imported by name)."""
    return _load(bench_dir / "metrics" / f"{name}.py",
                 f"bench_metric_{name.replace('.', '_')}").read


def loop_class(kind: str, bench_dir: Path = BENCH_DIR):
    """The ``Loop`` class of ``bench/loops/<kind>.py``."""
    path = bench_dir / "loops" / f"{kind}.py"
    if not path.is_file():
        raise SystemExit(f"no request loop {kind!r} (no {path.name} in "
                         f"{path.parent})")
    return _load(path, f"bench_loop_{kind}").Loop
