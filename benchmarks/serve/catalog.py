"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's
root names the cell, its configuration and its traffic mix; each of those
is a file of its own under this directory.

    configs/<config>.json      the configuration as it is run
    traffic/<mix>.json         the mix's parameters (``traffic.py`` reads it)
    metrics/<metric>.py        the reader of a per-layer metric; metrics
                               that differ only in their last part
                               (``mfu.chat``, ``mfu.code``) share
                               ``metrics/mfu.py`` unless one has its own
    references/<name>.py       the plain reference a configuration names
    limits/<cell>.json         the limits that decide ``correct``
    offered/<cell>.json        an open-loop cell's offered rate

Adding a cell, a mix, a configuration or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import dataclasses
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
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    limits = _json_or_empty(root / SUBDIR / "limits" / f"{name}.json")
    offered = _json_or_empty(root / SUBDIR / "offered" / f"{name}.json")
    return Cell(
        name=name, config_name=w["config"], traffic=w["traffic"],
        chips=int(w["chips"]), config=config,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        limits=limits, offered=offered)


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


def arch_config(config: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file: the
    published keys mapped onto the program's names, nothing else."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=config.get("model_type", "dense"), family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        d_head=config["head_dim"], qk_norm=bool(config["qk_norm"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype=config["torch_dtype"], act_dtype=config["torch_dtype"],
        use_pallas_kernels=bool(config["serving"]["use_pallas_kernels"]))
