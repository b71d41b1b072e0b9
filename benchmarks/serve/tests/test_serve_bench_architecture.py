"""A configuration of another family goes in by new files alone.

A scratch checkout holds the harness as it is, plus a configuration of
the program's ``family="moe"`` path (8 experts, top-2, 1 shared), its
architecture module (``data/moe_decoder.py``) and a cell in
``BENCHMARK.json``.  A process started there, so that the harness finds
everything from that checkout's root, builds the Engine from the cell
(the weights' layout checked against the program's), serves a few
requests through the paged path, and reads ``mfu`` with the
architecture's own counts."""
import json
import os
import shutil
import subprocess
import sys

from serve_bench_tiny import SERVE
import catalog

FIXTURE = SERVE / "tests" / "data" / "moe_decoder.py"
SEED = 2**34 + 77

CONFIG = {
    "architecture": "moe_decoder", "model_type": "tiny_moe",
    "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "vocab_size": 500,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "qk_norm": False,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "serving": {"max_len": 128, "max_batch": 4, "page_size": 16,
                "prefill_chunk": 0, "prefix_reuse": True,
                "use_pallas_kernels": True},
    "reduced": [],
}

# runs in the scratch checkout: argv[1] is its benchmarks/serve
SERVE_A_FEW = """
import json, sys
sys.path.insert(0, sys.argv[1])
import numpy as np
import catalog, run, serving, traffic, work

cell = catalog.find_cell("tiny-moe.chat_backlog")
engine = run.build_engine(cell, int(sys.argv[2]))
driver = serving.Driver(engine, cell.config["serving"]["max_batch"])
rng = np.random.default_rng(0)
for i, n in enumerate((16, 40, 23, 64, 31, 16)):
    prompt = rng.integers(0, cell.config["vocab_size"], n).astype(np.int32)
    driver.submit(traffic.Request(i, 0.0, prompt, 6), driver.clock())
t0 = driver.clock()
driver.drain()
t1 = driver.clock()
peak = work.peaks("TPU v5 lite")
view = run.RunView(cell.config, catalog.mix(cell), peak, driver.flows,
                   driver.steps, t0, t1, None)
arch = catalog.architecture(cell.config["architecture"])
flops = sum(arch.prefill_flops(cell.config, s.prefill_plen)
            for s in driver.steps if s.prefill_plen)
flops += sum(arch.decode_flops(cell.config, c)
             for s in driver.steps for c in s.contexts)
print(json.dumps({
    "root": str(catalog.ROOT),
    "arch_file": arch.__file__,
    "family": catalog.arch_config(cell.config).family,
    "paged_decode": engine.scheduler_report()["paged_decode"],
    "states": [f.state for f in driver.flows],
    "tokens": [len(f.handle.out_tokens) for f in driver.flows],
    "prefills": sum(1 for s in driver.steps if s.prefill_plen),
    "decoded": sum(len(s.contexts) for s in driver.steps),
    "mfu": catalog.metric_reader("mfu.chat").read(view),
    "mfu_from_counts": 100.0 * flops / ((t1 - t0)
                                        * peak["bf16_flops_per_s"]),
    "decode_flops_at_100": work.decode_flops(cell.config, 100),
    "paged_call": work.paged_attention_call(cell.config, [100, 300]),
}))
"""


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    serve = root / catalog.SUBDIR
    shutil.copytree(SERVE, serve,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(FIXTURE, serve / "architectures" / "moe_decoder.py")
    (serve / "configs" / "tiny-moe.json").write_text(json.dumps(CONFIG))
    bench = catalog.load_benchmark()
    bench["configs"].append({
        "name": "tiny-moe", "source": "test",
        "file": str(catalog.SUBDIR / "configs" / "tiny-moe.json"),
        "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "tiny-moe.chat_backlog", "config": "tiny-moe",
        "traffic": "chat_backlog", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_moe_configuration_is_served_from_new_files_alone(tmp_path):
    root = _checkout(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(catalog.ROOT / "src"),
               REPRO_AUTOTUNE_CACHE=str(tmp_path / "autotune.json"))
    out = subprocess.run(
        [sys.executable, "-c", SERVE_A_FEW, str(root / catalog.SUBDIR),
         str(SEED)], cwd=root, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["root"] == str(root)
    assert got["arch_file"] == str(
        root / catalog.SUBDIR / "architectures" / "moe_decoder.py")
    assert got["family"] == "moe" and got["paged_decode"]
    assert got["states"] == ["done"] * 6 and got["tokens"] == [6] * 6
    assert got["prefills"] == 6 and got["decoded"] == 6 * 5
    assert got["mfu"] > 0 and got["mfu"] == got["mfu_from_counts"]
    # by hand: per layer q, k, v, o (2*64*64 + 2*64*32), the router
    # (64*8) and 3 experts' SwiGLU (3 * 3*64*32); the head 2*64*500
    per_token = 2 * 64 * 64 + 2 * 64 * 32 + 64 * 8 + 3 * 3 * 64 * 32
    assert got["decode_flops_at_100"] == \
        2 * (2 * per_token + 4 * 4 * 16 * 100) + 2 * 64 * 500
    # two layers: K and V of 400 positions at 2 heads of 16, bf16, and
    # each row's query and output at 4 heads
    assert got["paged_call"] == [
        2 * 4 * 4 * 16 * 400,
        2 * (2 * 2 * 400 * 16 * 2 + 2 * 2 * 4 * 16 * 2)]
    # every file the harness already had is untouched
    for path in SERVE.rglob("*"):
        if path.is_file() and ".cache" not in path.parts \
                and "__pycache__" not in path.parts:
            copy = root / catalog.SUBDIR / path.relative_to(SERVE)
            assert copy.read_bytes() == path.read_bytes()
