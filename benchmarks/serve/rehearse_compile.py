#!/usr/bin/env python3
"""Compiles a configuration's served programs for a TPU v5e, on the CPU.

    JAX_PLATFORMS=cpu python3 benchmarks/serve/rehearse_compile.py \
        mistral-nemo-12b

For each prompt length of the mixes that the configuration's cells use,
the engine's whole-prompt prefill, and the scheduler's paged decode step,
are lowered at the configuration's sizes against a described
``v5e:2x2`` topology and compiled by the chip's compiler.  Each prints
its ``memory_analysis()`` and its count of Pallas kernels
(``tpu_custom_call``).  A compile that passes is not a chip run; it shows
what the compiler would refuse (tiling, VMEM, a program that does not
fit) before chip time is spent.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(config_name: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import catalog

    bench = catalog.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[config_name]
    config = catalog.load_config(catalog.ROOT / entry["file"])
    arch = catalog.architecture(config["architecture"])
    lengths = sorted({n for w in bench["workloads"]
                      if w["config"] == config_name
                      for n in catalog.mix(catalog.find_cell(w["name"])
                                           ).prompt_lengths})
    jax.config.update("jax_enable_compilation_cache", False)
    # the model picks its Pallas kernels only where it sees a TPU
    jax.default_backend = lambda: "tpu"
    from repro.models import layers, lm  # noqa: F401
    from repro.kernels import ops
    ops._on_tpu = lambda: True

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = arch.arch_config(config)
    s = config["serving"]

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    params = sds(jax.eval_shape(lambda k: arch.build(k, config),
                                jax.random.PRNGKey(0)))

    def report(name, fn, *args):
        t0 = time.monotonic()
        compiled = jax.jit(fn).lower(*args).compile()
        m = compiled.memory_analysis()
        print(f"{name}: compiled in {time.monotonic() - t0:.1f} s; "
              f"{compiled.as_text().count('tpu_custom_call')} "
              f"tpu_custom_call; arguments {m.argument_size_in_bytes} B, "
              f"outputs {m.output_size_in_bytes} B, temporaries "
              f"{m.temp_size_in_bytes} B, aliased {m.alias_size_in_bytes} B",
              flush=True)

    for n in lengths:
        report(f"prefill[1x{n}]",
               lambda p, t: lm.prefill(p, t, cfg, max_len=s["max_len"]),
               params, jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=chip))
    mb, ps = s["max_batch"], s["page_size"]
    n_pages = mb * s["max_len"] // ps
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, cfg.n_kv_heads, n_pages + 1, ps, cfg.d_head),
        jnp.dtype(config["torch_dtype"]), sharding=chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    report(f"paged_decode[{mb} rows]",
           lambda p, kp, vp, t, tab, kv, wp, wo: lm.paged_decode_step(
               p, kp, vp, t, tab, kv, wp, wo, cfg),
           params, pool, pool, i32(mb, 1), i32(mb, s["max_len"] // ps),
           i32(mb), i32(mb), i32(mb))
    pool_bytes = 2 * pool.size * pool.dtype.itemsize
    print(f"{config_name}: parameters "
          f"{sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))}"
          f" B, K and V pools {pool_bytes} B", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "mistral-nemo-12b"))
