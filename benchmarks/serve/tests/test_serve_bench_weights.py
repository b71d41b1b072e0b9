"""Seeded weights in the program's layout, made again layer by layer by
the reference, and the reference's forward against the program's at a
tiny float32 size."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serve_bench_tiny as tiny
from serve_bench_tiny import SERVE
import catalog
import weights

from repro.models import lm

DENSE = catalog.architecture("dense_decoder")


@pytest.mark.parametrize("tied", [True, False])
def test_the_tree_has_the_programs_layout(tied):
    config = tiny.config(tied)
    cfg = catalog.arch_config(config)
    params = weights.make_params(config, 3)
    weights.check_layout(params, jax.eval_shape(
        lambda k: lm.init_model(cfg, k), jax.random.PRNGKey(0)))
    table = np.asarray(params["embed"]["table"], np.float32)
    assert table.shape[0] == 512 and not table[500:].any()


def test_a_layer_made_alone_equals_its_stacked_copy():
    config = tiny.config(False)
    seed = 2**41 + 9
    params = weights.make_params(config, seed)
    key = weights.root_key(seed)
    for i in range(config["num_hidden_layers"]):
        alone = jax.jit(lambda k: DENSE.layer(k, config))(
            weights.layer_key(key, i))
        stacked = jax.tree.map(lambda a: a[i], params["layers"])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool(jnp.array_equal(a, b)), alone, stacked))


def test_seeds_past_32_bits_give_other_weights():
    a = weights.root_key(7)
    b = weights.root_key(2**32 + 7)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(weights.root_key(7)), np.asarray(a))


@pytest.mark.parametrize("tied", [True, False])
def test_reference_forward_matches_the_program_in_float32(tied):
    """At float32 the plain reference and the program's XLA path compute
    the same function: last-position logits agree to rounding."""
    config = tiny.config(tied, dtype="float32")
    cfg = catalog.arch_config(config)
    seed = 123
    params = weights.make_params(config, seed)
    ref = catalog.reference("dense_decoder")
    rng = np.random.default_rng(0)
    for plen in (5, 37):
        prompt = rng.integers(0, config["vocab_size"], plen).astype(np.int32)
        want, _ = lm.prefill(params, jnp.asarray(prompt[None]), cfg,
                             max_len=64)
        want = np.asarray(want[0, :config["vocab_size"]])
        # teacher-forced on the program's own greedy token
        tok = int(np.argmax(want))
        (scored,) = ref.score(config, seed, [(prompt, [tok])])
        assert scored["best"][0] == pytest.approx(want.max(), abs=1e-4)
        assert scored["at"][0] == pytest.approx(want[tok], abs=1e-4)


def test_fp8_rounding_keeps_four_significant_bits():
    """e4m3 stores 3 bits after the leading one: steps of 1/8 in [1, 2)."""
    ref = catalog.reference("dense_decoder")
    x = jnp.asarray([1.0, 1.125, 1.0625, 1.03125, 3.0, -0.3, 1e-3],
                    jnp.float32)
    got = np.asarray(ref.fp8_round(x))
    assert list(got[:5]) == [1.0, 1.125, 1.0, 1.0, 3.0]   # 1.0625: to even
    assert abs(got[5] + 0.3) <= 0.3 / 16 and abs(got[6] - 1e-3) <= 1e-3 / 16


# Digests of seeded weights, recorded on the CPU before the dense layer
# code moved from weights.py into architectures/dense_decoder.py: the
# move keeps every bit.
PIN_SEED = 2**33 + 1015
TINY_TREE_DIGEST = {
    True: "4ea26b99bcb8ed9f5d3d0d3ba5e37c6fa97b66f662feff030ab305f1ce356317",
    False: "a347788a29e8739c5e90b5f6a3a47a2caad9bf582f0dfebeb555229625d35482",
}
LAYER0_DIGEST = {
    "qwen3-1.7b":
        "7c6714f3fa350fdaf0ec7a4fb5f5f13c4c65ee2c8770296693596bdd1b062052",
    "mistral-nemo-12b":
        "b833908dbe2bd8fc53dc698de24a3b3db17c05bdcfc824355bf7a77ecf6cadb2",
}
TABLE_ROWS_DIGEST = {
    "qwen3-1.7b":
        "c15b2286ea65ce9096ecfd2243792458aef360a6874460ba3a8d5e61e7e0cdc8",
    "mistral-nemo-12b":
        "30659edfc513cff1c5ec7c6eee07407e39a9bb736a48b4859d5b9047bd99eeea",
}
PINNED_ROWS = 256


def _bench_config(name):
    return json.loads((SERVE / "configs" / f"{name}.json").read_text())


def _digest(leaves):
    """sha256 over each (path, leaf): its path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, leaf in leaves:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("tied", [True, False])
def test_the_tiny_tree_is_pinned(tied):
    params = weights.make_params(tiny.config(tied), PIN_SEED)
    assert _digest(jax.tree_util.tree_flatten_with_path(params)[0]) \
        == TINY_TREE_DIGEST[tied]


@pytest.mark.parametrize("name", sorted(LAYER0_DIGEST))
def test_layer_zero_of_each_benchmark_configuration_is_pinned(name):
    """At the configuration's widths; made one leaf at a time, so that at
    most one matrix is held."""
    config = _bench_config(name)
    key = weights.layer_key(weights.root_key(PIN_SEED), 0)
    shapes = jax.eval_shape(lambda k: DENSE.layer(k, config), key)
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    leaves = [(p, jax.jit(lambda k, i=i: jax.tree.leaves(
        DENSE.layer(k, config))[i])(key)) for i, p in enumerate(paths)]
    assert _digest(leaves) == LAYER0_DIGEST[name]


@pytest.mark.parametrize("name", sorted(TABLE_ROWS_DIGEST))
def test_the_first_table_rows_of_each_benchmark_configuration_are_pinned(
        name):
    """The first rows of the embedding and the head, at the
    configuration's width.  Rows are drawn by their position in the
    table, so the first rows of a table cut to ``PINNED_ROWS`` are those
    of the whole one (checked at the tiny size below); the whole tables
    would take gigabytes on the CPU."""
    config = dict(_bench_config(name), vocab_size=PINNED_ROWS)
    key = weights.root_key(PIN_SEED)
    rows = jax.jit(lambda k: {"embed": weights.embed_table(k, config),
                              "head": weights.head_table(k, config)})(key)
    assert _digest(jax.tree_util.tree_flatten_with_path(rows)[0]) \
        == TABLE_ROWS_DIGEST[name]
    small = tiny.config(False)
    whole = weights.head_table(key, small)
    cut = weights.head_table(key, dict(small, vocab_size=64))
    assert np.array_equal(np.asarray(whole[:64], np.float32),
                          np.asarray(cut[:64], np.float32))
