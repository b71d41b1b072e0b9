"""Compile the served kernels for a TPU v5e at qwen3-1.7b widths.

Interpret mode cannot see what only the chip's compiler checks: tiles
that do not align to the hardware tiling, kernels that ask for more
VMEM than they may use.  These tests lower each kernel that
``chip_smoke.py`` serves through, at its shapes, against a described
v5e topology (no chip needed), and require a Mosaic kernel
(``tpu_custom_call``) in the compiled program.

Every compile against the topology stays in this one file: only one
process may load the TPU compiler's library, so the description is made
inside a module fixture, never at import, and a test file on another
xdist worker would skip in silence.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops

CFG = configs.get("qwen3-1.7b")
# the serving shapes of chip_smoke.py
MAX_BATCH = 8
PAGE_SIZE = 16
MAX_LEN = 2048
PROMPT_LENS = (128, 512)
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _custom_calls(lowered) -> int:
    return lowered.compile().as_text().count("tpu_custom_call")


# the serving benchmark's decode shapes: max_batch 16, max_len 2048,
# page 16, d_head 128; q / KV heads of qwen3-1.7b and mistral-nemo-12b
BENCH_BATCH = 16
BENCH_DECODE_HEADS = {"qwen3-1.7b": (16, 8), "mistral-nemo-12b": (32, 8)}


@pytest.mark.parametrize("model", sorted(BENCH_DECODE_HEADS))
def test_paged_decode_attention_compiles(one_chip, model):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hq, hkv = BENCH_DECODE_HEADS[model]
    d = CFG.d_head
    n_pages = BENCH_BATCH * MAX_LEN // PAGE_SIZE
    pool = s((hkv, n_pages + 1, PAGE_SIZE, d), BF16)
    lowered = ops.paged_attention.lower(
        s((BENCH_BATCH, hq, 1, d), BF16), pool, pool,
        s((BENCH_BATCH, MAX_LEN // PAGE_SIZE), I32), s((BENCH_BATCH,), I32),
        scale=d ** -0.5, backend="pallas")
    assert _custom_calls(lowered) == 1


@pytest.mark.parametrize("seq", PROMPT_LENS)
def test_prefill_attention_compiles(one_chip, seq):
    q = jax.ShapeDtypeStruct((1, CFG.n_heads, seq, CFG.d_head), BF16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, CFG.n_kv_heads, seq, CFG.d_head), BF16,
                              sharding=one_chip)
    lowered = ops.attention.lower(q, kv, kv, causal=True,
                                  scale=CFG.d_head ** -0.5,
                                  backend="pallas")
    assert _custom_calls(lowered) >= 1


@pytest.mark.parametrize("m", (MAX_BATCH,) + PROMPT_LENS)
@pytest.mark.parametrize("k,n,activation", [
    (CFG.d_model, CFG.d_ff, "silu"),     # gate projection
    (CFG.d_ff, CFG.d_model, None),       # down projection
])
def test_mlp_matmul_compiles(one_chip, m, k, n, activation):
    a = jax.ShapeDtypeStruct((m, k), BF16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), BF16, sharding=one_chip)
    lowered = ops.matmul_fused.lower(a, w, activation=activation,
                                     backend="pallas")
    assert _custom_calls(lowered) >= 1
