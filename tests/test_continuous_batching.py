"""PR 8: continuous batching on a paged, per-row-banded KV cache.

Four layers under test, bottom-up:

  * kernels — per-row banding: ``kv_len`` as a (B,) vector bands both
    attention anchors per batch row (rows at 0 and at the full buffer
    included), and ``ops.paged_attention`` reads scattered pages
    through the block-table index map with contiguous-equivalent
    results;
  * cost model — a ragged decode step's modeled traffic follows each
    row's valid length, not the batch max;
  * ops API — ``SpecOverride`` as the one spec-shaped door into all
    four entry points, with the old per-op kwargs kept as aliases;
  * engine — reach-aware admission, mixed-length batches through the
    continuous scheduler with bit-identical greedy tokens vs
    per-request sequential decode, prefix-page reuse, chunked prefill,
    the handle/stream API, and the deprecated ``generate`` shim.
"""
import dataclasses
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import cost_model
from repro.core.dataflow import (AttentionProblem, DataflowSpec,
                                 SpecOverride, OS, WS, IS)
from repro.kernels import ops, ref
from repro.models import lm
from repro.serve.engine import (AdmissionError, Engine, RequestState,
                                StepFailed)
from repro.serve.paged_cache import PagedKVCache, pages_for
from repro.serve.scheduler import SamplingParams, SchedulerConfig

CFG = configs.get_smoke("qwen3-1.7b")
MAX_LEN = 48


@pytest.fixture(scope="module")
def params():
    return lm.init_model(CFG, jax.random.PRNGKey(0))


def _qkv(b, hq, hkv, sq, skv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), jnp.float32)
    return q, k, v


# ---------------------------------------------------------------------------
# Kernels: per-row banding, both anchors.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("anchor", ["os", "ws"])
def test_ragged_rowwise_banding_parity(anchor):
    # rows at 0, mid-band, block-unaligned, and the full buffer
    skv = 32
    kv = jnp.asarray([0, 5, 17, skv], jnp.int32)
    q, k, v = _qkv(4, 2, 2, 1, skv, 16)
    got = ops.attention(q, k, v, causal=True, backend="interpret",
                        anchor=anchor, bq=8, bkv=8, kv_len=kv)
    want = ref.attention_ref(q, k, v, causal=True, kv_len=kv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # a row with no valid KV attends to nothing
    assert np.all(np.asarray(got)[0] == 0.0)


@pytest.mark.parametrize("anchor", ["os", "ws"])
def test_ragged_banding_with_window(anchor):
    skv = 32
    kv = jnp.asarray([3, 12, 32], jnp.int32)
    q, k, v = _qkv(3, 2, 1, 1, skv, 16, seed=1)   # GQA group=2
    got = ops.attention(q, k, v, causal=True, window=8,
                        backend="interpret", anchor=anchor, bq=8,
                        bkv=8, kv_len=kv)
    want = ref.attention_ref(q, k, v, causal=True, window=8, kv_len=kv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_vs_contiguous_equivalence():
    # one pool, three requests of ragged length; kernel reads pages
    # through the block table, oracle reads the contiguous originals
    page, d, hkv, hq = 8, 16, 2, 4
    kv = np.asarray([5, 17, 24], np.int32)
    q, k, v = _qkv(3, hq, hkv, 1, int(kv.max()), d, seed=2)
    n_pages = sum(pages_for(int(n), page) for n in kv) + 1
    pool_k = np.zeros((hkv, n_pages, page, d), np.float32)
    pool_v = np.zeros((hkv, n_pages, page, d), np.float32)
    tables = np.zeros((3, pages_for(int(kv.max()), page)), np.int32)
    nxt = 1                                 # page 0 stays as padding
    for r, n in enumerate(kv):
        for j in range(pages_for(int(n), page)):
            lo, hi = j * page, min((j + 1) * page, int(n))
            pool_k[:, nxt, :hi - lo] = np.asarray(k)[r, :, lo:hi]
            pool_v[:, nxt, :hi - lo] = np.asarray(v)[r, :, lo:hi]
            tables[r, j] = nxt
            nxt += 1
    for backend in ("interpret", "xla"):
        got = ops.paged_attention(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(tables), jnp.asarray(kv), backend=backend)
        want = ref.attention_ref(q, k, v, causal=True,
                                 kv_len=jnp.asarray(kv))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"backend={backend}")


def _paged_pool(k, v, kv, page, max_pages, seed=0):
    """The rows' K/V scattered over a shuffled pool; table pad slots
    hold page 0, which is filled with NaN so that any read of it shows."""
    hkv, d = k.shape[1], k.shape[-1]
    n_pages = sum(pages_for(int(n), page) for n in kv) + 1
    pool_k = np.zeros((hkv, n_pages, page, d), np.float32)
    pool_v = np.zeros((hkv, n_pages, page, d), np.float32)
    pool_k[:, 0] = pool_v[:, 0] = np.nan
    tables = np.zeros((len(kv), max_pages), np.int32)
    ids = iter(np.random.default_rng(seed).permutation(n_pages - 1) + 1)
    for r, n in enumerate(kv):
        for j in range(pages_for(int(n), page)):
            lo, hi = j * page, min((j + 1) * page, int(n))
            pid = next(ids)
            pool_k[:, pid, :hi - lo] = np.asarray(k)[r, :, lo:hi]
            pool_v[:, pid, :hi - lo] = np.asarray(v)[r, :, lo:hi]
            tables[r, j] = pid
    return (jnp.asarray(pool_k, k.dtype), jnp.asarray(pool_v, v.dtype),
            jnp.asarray(tables))


# page 8 with d 16: blocks of 16 pages (128 positions); 40 pages a row
PAGED_MAX_LEN = 320
RAGGED = [0, 1, 8, 9, 300, PAGED_MAX_LEN]
PAGED_WALKS = {
    # ragged lengths: empty, one position, one page, a page and one,
    # three blocks with a partial last one, the whole table
    **{f"ragged-group{g}": (g, RAGGED, None, jnp.float32)
       for g in (1, 2, 4)},
    # the band starts mid-page and mid-block (300 - 150) and spans
    # two blocks
    "window": (2, [5, 40, 300, PAGED_MAX_LEN], 150, jnp.float32),
    "all-empty": (2, [0, 0, 0], None, jnp.float32),
    # bf16 pools: p meets V unrounded, so float32 queries still get
    # float32 results
    "bf16-pool": (2, RAGGED, None, jnp.bfloat16),
}


@pytest.mark.parametrize("walk", sorted(PAGED_WALKS))
def test_paged_attention_walk(walk):
    group, lens, window, pool_dtype = PAGED_WALKS[walk]
    page, d, hkv = 8, 16, 2
    kv = np.asarray(lens, np.int32)
    q, k, v = _qkv(len(kv), hkv * group, hkv, 1, PAGED_MAX_LEN, d, seed=3)
    k, v = k.astype(pool_dtype), v.astype(pool_dtype)
    pool_k, pool_v, tables = _paged_pool(k, v, kv, page,
                                         PAGED_MAX_LEN // page)
    got = ops.paged_attention(q, pool_k, pool_v, tables, jnp.asarray(kv),
                              window=window, backend="interpret")
    want = ref.attention_ref(q, k.astype(jnp.float32),
                             v.astype(jnp.float32), causal=True,
                             window=window, kv_len=jnp.asarray(kv))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # a row with no valid KV attends to nothing
    assert np.all(np.asarray(got)[kv == 0] == 0.0)


def test_paged_attention_rejects_prefill_queries():
    q = jnp.zeros((1, 2, 4, 16))            # Sq=4: not a decode step
    pool = jnp.zeros((2, 4, 8, 16))
    with pytest.raises(ValueError, match="decode-only"):
        ops.paged_attention(q, pool, pool, jnp.zeros((1, 4), jnp.int32),
                            jnp.asarray([8], jnp.int32))


# ---------------------------------------------------------------------------
# Cost model: ragged decode traffic follows kv_valid, not batch max.
# ---------------------------------------------------------------------------
def test_ragged_decode_traffic_scales_per_row():
    p = AttentionProblem(bh=8, sq=1, skv=1024, d=64, group=1,
                        causal=True, dtype="float32", rows=4)
    spec = DataflowSpec.basic(OS, block=(8, 128, 64))
    short = cost_model.attention_rows_traffic(
        p, [64, 64, 64, 64], spec).total
    ragged = cost_model.attention_rows_traffic(
        p, [64, 256, 512, 1024], spec).total
    worst = cost_model.attention_rows_traffic(
        p, [1024, 1024, 1024, 1024], spec).total
    assert short < ragged < worst
    # a batch-max model would bill every row at the longest request
    assert ragged < 0.75 * worst


# ---------------------------------------------------------------------------
# Ops API: SpecOverride across all four entry points.
# ---------------------------------------------------------------------------
def test_spec_override_matmul():
    a = jnp.asarray(np.random.default_rng(0).normal(size=(32, 24)),
                    jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).normal(size=(24, 16)),
                    jnp.float32)
    want = a @ b
    for ov in (SpecOverride(anchor=WS),
               SpecOverride(block=(16, 8, 8)),
               SpecOverride(anchor=OS, block=(16, 8, 8))):
        got = ops.matmul(a, b, spec=ov, backend="interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)


def test_spec_override_attention_and_kwarg_aliases():
    q, k, v = _qkv(2, 2, 2, 8, 8, 16, seed=3)
    want = ref.attention_ref(q, k, v, causal=True)
    via_override = ops.attention(
        q, k, v, causal=True, backend="interpret",
        spec=SpecOverride(anchor=WS, block=(8, 8)))
    via_kwargs = ops.attention(q, k, v, causal=True,
                               backend="interpret", anchor="ws",
                               bq=8, bkv=8)
    np.testing.assert_allclose(np.asarray(via_override),
                               np.asarray(want), atol=2e-5, rtol=2e-5)
    # the override is sugar for the old kwargs: identical results
    np.testing.assert_array_equal(np.asarray(via_override),
                                  np.asarray(via_kwargs))
    with pytest.raises(ValueError, match="OS/WS"):
        ops.attention(q, k, v, backend="interpret",
                      spec=SpecOverride(anchor=IS))


def test_spec_override_conv2d():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 12, 12, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 8, 16)), jnp.float32)
    want = ops.conv2d(x, w, backend="xla")
    got = ops.conv2d(x, w, backend="interpret",
                     spec=SpecOverride(anchor=OS))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_spec_override_merge_semantics():
    base = DataflowSpec.optimized()
    merged = SpecOverride(anchor=WS).merge(base)
    assert merged.anchor == WS
    assert merged.block == base.block
    full = SpecOverride(anchor=OS, block=(64, 32, 16))
    assert full.is_complete
    assert full.merge(base).block == (64, 32, 16)
    partial = SpecOverride(block=(None, 256, None)).merge(base)
    assert partial.block == (base.block[0], 256, base.block[2])


# ---------------------------------------------------------------------------
# Paged cache bookkeeping.
# ---------------------------------------------------------------------------
def test_paged_cache_refcounts_and_prefix_chain():
    cache = PagedKVCache(CFG, n_pages=8, page_size=4)
    toks = list(range(10))                 # 2 full pages + 1 partial
    pages = cache.alloc(pages_for(10, 4))
    assert pages is not None and len(pages) == 3
    L, H, D = CFG.n_layers, CFG.n_kv_heads, CFG.d_head
    kv = jnp.ones((L, H, 10, D))
    cache.store(toks, pages, 0, kv, kv)
    # a second prompt sharing the first 8 tokens reuses both full pages
    reuse, covered = cache.lookup_prefix(list(range(8)) + [99, 98, 97])
    assert covered == 8 and reuse == pages[:2]
    assert cache.refs[pages[0]] == 2
    # chain key includes the parent: same chunk at a different start
    # position (or after a different first page) must not match
    miss, cov0 = cache.lookup_prefix([4, 5, 6, 7, 0, 1, 2, 3, 42])
    assert cov0 == 0 and miss == []
    cache.release(reuse)
    cache.release(pages)
    assert cache.free_pages == 8
    # freed pages leave the prefix chain
    gone, _ = cache.lookup_prefix(toks)
    assert gone == []


def test_paged_cache_alloc_exhaustion_is_total():
    cache = PagedKVCache(CFG, n_pages=2, page_size=4)
    assert cache.alloc(3) is None          # no partial allocation
    assert cache.free_pages == 2
    assert cache.stats["oom_rejects"] == 1
    got = cache.alloc(2)
    assert len(got) == 2 and not cache.can_admit(1)


# ---------------------------------------------------------------------------
# Engine: reach-aware admission (the PR-8 bugfix).
# ---------------------------------------------------------------------------
def test_admission_probes_request_reach_not_max_len(params):
    # 64 KiB VMEM: the decode-step attention fits at the request's kv
    # reach (12) but not at max_len (2048).  The old probe billed every
    # request for max_len and over-rejected exactly this case.
    hw = dataclasses.replace(cost_model.V5E, vmem_bytes=65536,
                             name="tiny-vmem-64k")
    eng = Engine(CFG, params, max_len=2048, hw=hw)
    req = eng.submit(np.zeros(8, np.int32), 4)    # reach = 12: fits
    assert req.state == RequestState.QUEUED
    with pytest.raises(AdmissionError, match="kv reach"):
        eng.submit(np.zeros(8, np.int32), 4096)   # reach = 2048: doesn't


# ---------------------------------------------------------------------------
# Engine: continuous scheduler end to end.
# ---------------------------------------------------------------------------
def _sequential_tokens(params, prompts, new_tokens):
    out = []
    for p in prompts:
        eng = Engine(CFG, params, max_len=MAX_LEN)
        r = eng.submit(p, new_tokens)
        eng.serve([r])
        assert r.state == RequestState.DONE
        out.append(list(r.out_tokens))
    return out


def _ragged_prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def test_mixed_length_batch_matches_sequential_decode(params):
    # the PR-8 acceptance property: a ragged batch through the
    # continuous scheduler produces the same greedy tokens as decoding
    # each request alone
    prompts = _ragged_prompts([7, 12, 2, 23])
    want = _sequential_tokens(params, prompts, 5)
    eng = Engine(CFG, params, max_len=MAX_LEN)
    reqs = [eng.submit(p, 5) for p in prompts]
    eng.serve(reqs)
    for r, exp in zip(reqs, want):
        assert r.state == RequestState.DONE, (r.rid, r.state, r.error)
        assert list(r.out_tokens) == exp
    rep = eng.scheduler_report()
    assert rep["steps"] > 0 and rep["active"] == 0


def test_continuous_slots_turn_over(params):
    # more requests than slots: short ones finish and free their slot
    # for the queue, and everyone still matches the sequential oracle
    prompts = _ragged_prompts([3, 9, 4, 6, 11], seed=1)
    want = _sequential_tokens(params, prompts, 3)
    eng = Engine(CFG, params, max_len=MAX_LEN,
                 scheduler_config=SchedulerConfig(max_batch=2))
    reqs = [eng.submit(p, 3) for p in prompts]
    eng.serve(reqs)
    for r, exp in zip(reqs, want):
        assert r.state == RequestState.DONE
        assert list(r.out_tokens) == exp


def test_chunked_prefill_matches_whole_prefill(params):
    prompts = _ragged_prompts([19, 7], seed=2)
    want = _sequential_tokens(params, prompts, 4)
    eng = Engine(CFG, params, max_len=MAX_LEN,
                 scheduler_config=SchedulerConfig(max_batch=2,
                                                  prefill_chunk=8))
    reqs = [eng.submit(p, 4) for p in prompts]
    eng.serve(reqs)
    for r, exp in zip(reqs, want):
        assert r.state == RequestState.DONE
        assert list(r.out_tokens) == exp


def test_prefix_page_reuse_shares_and_matches(params):
    rng = np.random.default_rng(3)
    shared = rng.integers(0, CFG.vocab_size, (19,)).astype(np.int32)
    p1 = shared
    p2 = np.concatenate(
        [shared[:16],
         rng.integers(0, CFG.vocab_size, (5,)).astype(np.int32)])
    want = _sequential_tokens(params, [p1, p2], 4)
    eng = Engine(CFG, params, max_len=MAX_LEN,
                 scheduler_config=SchedulerConfig(max_batch=2,
                                                  page_size=8))
    reqs = [eng.submit(p, 4) for p in (p1, p2)]
    eng.serve(reqs)
    for r, exp in zip(reqs, want):
        assert r.state == RequestState.DONE
        assert list(r.out_tokens) == exp
    pages = eng.scheduler_report()["pages"]
    assert pages["reuse_hits"] == 1
    assert pages["reuse_pages"] == 2       # 16 shared positions / 8


def test_handle_stream_and_result(params):
    prompts = _ragged_prompts([7, 12], seed=4)
    want = _sequential_tokens(params, prompts, 4)
    eng = Engine(CFG, params, max_len=MAX_LEN)
    h1 = eng.submit(prompts[0], 4)
    h2 = eng.submit(prompts[1], 4)
    # streaming h1 steps the scheduler; h2 decodes alongside it
    assert list(h1.tokens()) == want[0]
    assert list(h2.result()) == want[1]
    assert h1.state == RequestState.DONE
    assert h2.state == RequestState.DONE


def test_sampling_params_bundle(params):
    prompts = _ragged_prompts([6], seed=5)
    eng = Engine(CFG, params, max_len=MAX_LEN)
    h = eng.submit(prompts[0],
                   sampling=SamplingParams(max_new_tokens=3,
                                           greedy=False, seed=7))
    toks = h.result()
    assert len(toks) == 3
    # same per-request seed, fresh engine: the sampled stream replays
    eng2 = Engine(CFG, params, max_len=MAX_LEN)
    h2 = eng2.submit(prompts[0],
                     sampling=SamplingParams(max_new_tokens=3,
                                             greedy=False, seed=7))
    np.testing.assert_array_equal(toks, h2.result())


def test_generate_is_a_deprecated_shim(params):
    eng = Engine(CFG, params, max_len=MAX_LEN)
    prompts = np.stack(_ragged_prompts([8, 8], seed=6))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = eng.generate(prompts, max_new_tokens=3)
    assert any(issubclass(w.category, DeprecationWarning)
               for w in caught)
    assert out.shape == (2, 3)
    want = _sequential_tokens(params, list(prompts), 3)
    assert [list(row) for row in out] == want
