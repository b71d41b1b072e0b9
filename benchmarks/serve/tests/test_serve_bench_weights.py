"""Seeded weights in the program's layout, made again layer by layer by
the reference, and the reference's forward against the program's at a
tiny float32 size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serve_bench_tiny as tiny
import catalog
import weights

from repro.models import lm


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
        alone = jax.jit(lambda k: weights.layer(k, config))(
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
