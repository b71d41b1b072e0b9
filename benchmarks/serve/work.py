"""Work the algorithm needs, counted from shapes: operations and bytes
of the model's steps and kernels, and the chip's peaks.

Counts are of the work at each request's real length, never of a padded
buffer or a kernel's grid, so a kernel that walks empty pages reads as a
low share of its roofline.  The peaks come from ``peaks.json``, keyed by
``device_kind``; a kind missing there is an error.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

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


def _dims(c: Dict) -> Tuple[int, int, int, int, int, int, int]:
    return (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["num_hidden_layers"], c["vocab_size"])


def elem_bytes(c: Dict) -> int:
    return _BYTES[c["torch_dtype"]]


def layer_matmul_params(c: Dict) -> int:
    """Weights one token multiplies by in one layer: q, k, v, o, and the
    SwiGLU gate, up and down projections."""
    d, ff, nh, nkv, dh, _, _ = _dims(c)
    return d * nh * dh + 2 * d * nkv * dh + nh * dh * d + 3 * d * ff


def head_flops(c: Dict) -> float:
    """The output head for one position, over the real vocabulary."""
    d, _, _, _, _, _, v = _dims(c)
    return 2.0 * d * v


def prefill_flops(c: Dict, plen: int) -> float:
    """A whole-prompt prefill: every matmul over ``plen`` tokens, causal
    attention (QK and PV over the lower triangle) and one head row."""
    _, _, nh, _, dh, n_layers, _ = _dims(c)
    causal_pairs = plen * (plen + 1) / 2
    per_layer = (2.0 * plen * layer_matmul_params(c)
                 + 4.0 * nh * dh * causal_pairs)
    return n_layers * per_layer + head_flops(c)


def decode_flops(c: Dict, context: int) -> float:
    """One decoded token that attends over ``context`` positions."""
    _, _, nh, _, dh, n_layers, _ = _dims(c)
    per_layer = 2.0 * layer_matmul_params(c) + 4.0 * nh * dh * context
    return n_layers * per_layer + head_flops(c)


def paged_attention_call(c: Dict, contexts: List[int]) -> Tuple[float, float]:
    """One layer's paged decode attention over rows attending to
    ``contexts`` positions each: (flops, bytes).  Bytes are the K and V
    of those positions, each query and each output once."""
    _, _, nh, nkv, dh, _, _ = _dims(c)
    eb = elem_bytes(c)
    flops = sum(4.0 * nh * dh * ctx for ctx in contexts)
    nbytes = sum(2.0 * nkv * ctx * dh * eb + 2.0 * nh * dh * eb
                 for ctx in contexts)
    return flops, nbytes


def mlp_gemms(c: Dict, m: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of the three SwiGLU GEMMs over ``m`` tokens."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    return [(m, d, ff), (m, d, ff), (m, ff, d)]


def gemm(c: Dict, m: int, k: int, n: int) -> Tuple[float, float]:
    """(flops, bytes): 2*M*K*N, and each operand and the output once."""
    return 2.0 * m * k * n, float(m * k + k * n + m * n) * elem_bytes(c)
