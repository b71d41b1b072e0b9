"""Work the algorithm needs, counted from shapes: operations and bytes
of the model's steps and kernels, and the chip's peaks.

Counts are of the work at each request's real length, never of a padded
buffer or a kernel's grid, so a kernel that walks empty pages reads as a
low share of its roofline.  A model's counts come from its
configuration's architecture (``architectures/<name>.py``), since its
layers are its own; what every model shares stays here.  The peaks come
from ``peaks.json``, keyed by ``device_kind``; a kind missing there is an
error.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

import catalog

PEAKS = Path(__file__).resolve().parent / "peaks.json"
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]
                  ) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def elem_bytes(c: Dict) -> int:
    return _BYTES[c["torch_dtype"]]


def head_flops(c: Dict) -> float:
    """The output head for one position, over the real vocabulary."""
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def prefill_flops(c: Dict, plen: int) -> float:
    """A whole-prompt prefill of ``plen`` tokens."""
    return catalog.architecture(c["architecture"]).prefill_flops(c, plen)


def decode_flops(c: Dict, context: int) -> float:
    """One decoded token that attends over ``context`` positions."""
    return catalog.architecture(c["architecture"]).decode_flops(c, context)


def paged_attention_call(c: Dict, contexts: List[int]) -> Tuple[float, float]:
    """Paged decode attention over rows attending to ``contexts``
    positions each, summed over the layers that have it: (flops, bytes)."""
    return catalog.architecture(c["architecture"]).paged_attention_call(
        c, contexts)


def gemm(c: Dict, m: int, k: int, n: int) -> Tuple[float, float]:
    """(flops, bytes): 2*M*K*N, and each operand and the output once."""
    return 2.0 * m * k * n, float(m * k + k * n + m * n) * elem_bytes(c)
