"""A tiny cell for the benchmark's CPU tests: the harness end to end at a
size a test run holds, on the XLA path the model takes off the chip."""
import sys
from pathlib import Path

SERVE = Path(__file__).resolve().parents[1]
for _p in (str(SERVE.parents[1] / "src"), str(SERVE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import catalog  # noqa: E402
import traffic  # noqa: E402

# the limit the tiny cell's sound runs stay under and its fp8 control
# exceeds, read from this file's seeds on the CPU (see the tests)
TINY_GAP_LIMIT = 0.15


def config(tied: bool = True, dtype: str = "bfloat16") -> dict:
    return {"reference": "dense_decoder", "architecture": "dense_decoder",
            "model_type": "tiny",
            "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 500,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "qk_norm": tied,
            "tie_word_embeddings": tied, "torch_dtype": dtype,
            "serving": {"max_len": 256, "max_batch": 4, "page_size": 16,
                        "prefill_chunk": 0, "prefix_reuse": True,
                        "use_pallas_kernels": True}}


def mix(kind: str) -> traffic.Mix:
    if kind == "chat_backlog":
        return traffic.Mix("chat_backlog", "backlog", 0.0, 2,
                           ((16, .5), (32, .3), (64, .2)), 24, 0.5, 4, 48, 10)
    return traffic.Mix("code_open", "poisson", 20.0, 0,
                       ((32, .5), (64, .5)), 12, 0.5, 4, 24, 10,
                       period=10, path_seed=0)


def cell(kind: str, tied: bool = True) -> catalog.Cell:
    """The tiny cell reports what BENCHMARK.json's cells of its mix do."""
    bench = catalog.load_benchmark()
    of_mix = {w["name"] for w in bench["workloads"] if w["traffic"] == kind}

    def reports(m):
        return "workloads" not in m or bool(of_mix & set(m["workloads"]))

    return catalog.Cell(
        name=f"tiny.{kind}", config_name="tiny", traffic=kind, chips=1,
        config=config(tied),
        end_to_end=[m for m in bench["end_to_end"] if reports(m)],
        per_layer=[m for m in bench["per_layer"] if reports(m)],
        limits={"logit_gap_max": {"limit": TINY_GAP_LIMIT}},
        offered={"rate_per_s": 20.0} if kind == "code_open" else {})
