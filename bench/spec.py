"""Find a cell's pieces by name: ``BENCHMARK.json`` names a workload,
its configuration file and its traffic mix; a configuration names its
architecture module in ``arch/<name>.py``; each per-layer metric is a
reader in ``metrics/<name>.py``; ``peaks.json`` holds the chip's peaks.
Adding a cell, a metric or an architecture adds files and entries and
edits none."""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# packages that hold architecture modules: the benchmark's, and the
# architectures that only the tests serve
ARCH_PACKAGES = ("arch", "tests.arch")
DEFAULT_ARCH = "dense_decoder"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""
    name: str
    chips: int
    config: dict                 # configs/<config>.json
    traffic: dict                # traffic/<traffic>.json
    end_to_end: list             # metric entries that this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(bench_file)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in {bench_file.name}; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _reports(m, name)])


def architecture(config: dict):
    """The architecture module a configuration names (``"arch"``; a file
    without the key means ``dense_decoder``).  An unknown name is an
    error that lists the known ones, never a default."""
    return _arch_module(config.get("arch", DEFAULT_ARCH))


@functools.lru_cache(maxsize=None)
def _arch_module(name: str):
    where = [(p.stem, pkg) for pkg in ARCH_PACKAGES
             for p in BENCH_DIR.joinpath(*pkg.split(".")).glob("[!_]*.py")]
    found = [pkg for stem, pkg in where if stem == name]
    if len(found) != 1:
        raise KeyError(f"architecture {name!r} is "
                       f"{'named twice' if found else 'unknown'}; known: "
                       f"{sorted(stem for stem, _ in where)}")
    return importlib.import_module(f"bench.{found[0]}.{name}")


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                                f"at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
