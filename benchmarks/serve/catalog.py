"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's
root names the cell, its configuration and its traffic mix; each of those
is a file of its own under this directory.

    configs/<config>.json      the configuration as it is run; its
                               ``architecture`` and ``reference`` keys
                               name the two files below
    architectures/<name>.py    the harness's side of an architecture:
                               ``arch_config(config)``, the program's
                               ``ArchConfig``; ``build(key, config)``,
                               the seeded weight tree in the program's
                               layout (from ``weights.py``'s helpers);
                               ``prefill_flops(c, plen)``,
                               ``decode_flops(c, context)`` and
                               ``paged_attention_call(c, contexts)``,
                               summed over the layers, for ``work.py``;
                               and what a reader of its cells takes of
                               it (``mlp_gemms`` for the GEMM roofline)
    traffic/<mix>.json         the mix's parameters (``traffic.py`` reads it)
    metrics/<metric>.py        the reader of a per-layer metric; metrics
                               that differ only in their last part
                               (``mfu.chat``, ``mfu.code``) share
                               ``metrics/mfu.py`` unless one has its own
    references/<name>.py       the plain reference a configuration names
    limits/<cell>.json         the limits that decide ``correct``
    offered/<cell>.json        an open-loop cell's offered rate

Adding a cell, a mix, a configuration, an architecture or a metric adds
files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SUBDIR = HERE.relative_to(ROOT)          # benchmarks/serve


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic: str
    chips: int
    config: Dict[str, Any]             # configs/<config>.json
    end_to_end: List[Dict[str, Any]]   # the end-to-end metrics it reports
    per_layer: List[Dict[str, Any]]    # the per-layer metrics it reports
    limits: Dict[str, Any]             # limits/<cell>.json, {} when absent
    offered: Dict[str, Any]            # offered/<cell>.json, {} when absent


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_config(root / configs[w["config"]]["file"])
    limits = _json_or_empty(root / SUBDIR / "limits" / f"{name}.json")
    offered = _json_or_empty(root / SUBDIR / "offered" / f"{name}.json")
    return Cell(
        name=name, config_name=w["config"], traffic=w["traffic"],
        chips=int(w["chips"]), config=config,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        limits=limits, offered=offered)


def load_config(path: Path) -> Dict[str, Any]:
    """A configuration file; it has to name its architecture."""
    config = json.loads(path.read_text())
    if "architecture" not in config:
        raise ValueError(f"{path} names no \"architecture\": the file "
                         f"architectures/<name>.py its model is run with")
    return config


def _json_or_empty(path: Path) -> Dict[str, Any]:
    return json.loads(path.read_text()) if path.exists() else {}


def traffic_file(mix: str, root: Path = ROOT) -> Path:
    return root / SUBDIR / "traffic" / f"{mix}.json"


def mix(cell: Cell, root: Path = ROOT):
    """The cell's traffic mix, offered at the cell's own rate."""
    import traffic

    return traffic.load(traffic_file(cell.traffic, root), cell.traffic,
                        cell.offered.get("rate_per_s"))


def _load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, root: Path = ROOT) -> ModuleType:
    """``metrics/<name>.py``, else ``metrics/<name less its last part>.py``;
    its ``read(run)`` returns a number or None."""
    own = root / SUBDIR / "metrics" / f"{name}.py"
    shared = own.with_name(name.rsplit(".", 1)[0] + ".py")
    path = own if own.exists() or "." not in name else shared
    return _load_module(path, "serve_metric_" + path.stem.replace(".", "_"))


def reference(name: str, root: Path = ROOT) -> ModuleType:
    return _load_module(root / SUBDIR / "references" / f"{name}.py",
                        "serve_reference_" + name)


@functools.lru_cache(maxsize=None)
def architecture(name: str, root: Path = ROOT) -> ModuleType:
    """``architectures/<name>.py``, loaded once: what the harness needs
    of the architecture a configuration names."""
    return _load_module(root / SUBDIR / "architectures" / f"{name}.py",
                        "serve_architecture_" + name)


def arch_config(config: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file, from its
    architecture."""
    return architecture(config["architecture"]).arch_config(config)
