"""Continuous-batching scheduler: per-step admit / prefill / decode.

The batch-synchronous ``Engine.serve`` loop admits one equal-length
batch, prefills it once, and decodes until the *last* request finishes
— short requests ride along as dead rows and a new request waits for
the whole batch to drain.  ``ContinuousScheduler`` replaces that with
a slot machine over the ragged cache the PR-8 kernels understand:

  * each ``step()`` admits at most one waiting request into a free
    slot (whole-prompt prefill, or one chunk of a long prompt when
    ``prefill_chunk`` is set — chunked prefill interleaves with decode
    so running requests never stall behind a long prompt), then runs
    one vectorized decode step for every occupied slot;
  * requests finish (DONE / EVICTED / FAILED) individually: their slot
    frees immediately and the next waiting request takes it on the
    following step — no batch barrier;
  * for paged-decode-capable configs the page pool IS the decode
    datapath (PR-10 tentpole): each admitted prompt's KV is scattered
    into refcounted pages, full-page prefixes are shared across
    requests (``lookup_prefix``), and every decode step runs
    ``lm.paged_decode_step`` -> ``ops.paged_attention`` straight off
    the block tables — no contiguous slot cache exists, so pool
    occupancy is the true capacity signal.  Configs the paged step
    cannot express (SSM state, encoder-decoder, int8 KV, per-layer
    traced windows, ``max_len`` not page-aligned) keep the PR-8
    contiguous slot cache with best-effort page mirroring.

Memory pressure (the PR-10 tentpole) is handled by an explicit ladder,
coarse to fine:

  1. **watermark backpressure** — admission defers (the request stays
     QUEUED with ``queue_reason`` set, a ``backpressure`` counter and
     ledger event; never a silent fallback) while other requests hold
     pages and the pool is above ``high_watermark``, or when the
     prompt's pages cannot be allocated;
  2. **host spill** — when a decoding row cannot grow by one page, the
     coldest *other* active request (LRU by last decode step, ties to
     the youngest rid) is spilled: its private pages move to host
     numpy buffers (shared prefix pages stay pinned via the refcount),
     its slot frees, and it parks in ``paused``;
  3. **preemption** — if spilling cannot free a page, the youngest
     request holding pool memory is preempted: pages released, a
     fsync'd ``preempt`` record journaled, tokens stashed as replay
     expectations, and the request re-enqueued QUEUED.  Greedy (and
     position-keyed sampled) decode is deterministic, so the recompute
     regenerates bit-identical tokens — verified for free by the
     engine's ``replay_divergence`` check.

Spilled requests resume (``unspill`` round-trip, bit-exact) once a
slot is free and the pool is back below ``low_watermark`` (or idle);
they have priority over new admissions, and no request is ever
silently dropped from the paged path.

Determinism contract (what the ragged crash drill pins): admission
order is the enqueue order (rid order under ``Engine.drain``), slots
are assigned lowest-free-first, prefill uses the engine's own jitted
functions, and the ladder's victim choices are keyed on step counts
and rids only — so a cold journal replay that re-enqueues the same
rids walks the identical slot/batch/pressure evolution and
regenerates bit-identical greedy tokens.

Faults route through ``Engine._execute`` under the same
``serve.prefill`` / ``serve.decode_step`` injection sites as the
batch-synchronous loop, and the pool adds ``pool.alloc`` (simulated
OOM -> drives the ladder) and ``pool.spill`` (mid-spill crash drill),
so every registered drill exercises this loop unchanged.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers, lm
from repro.runtime import health, trace
from repro.serve.paged_cache import PagedKVCache, pages_for


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling settings for the handle/stream API."""
    max_new_tokens: int = 16
    greedy: bool = True
    seed: int = 0
    deadline_s: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Continuous-batching knobs.

    ``max_batch``     decode slots (cache rows) — fixed, so the decode
                      trace never re-specializes as requests come/go.
    ``prefill_chunk`` 0 prefills whole prompts in one shot (and reuses
                      the engine's jitted prefill — bit-identical to
                      the batch-sync loop); >0 streams prompts longer
                      than the chunk through ``lm.prefill_chunk`` one
                      chunk per step, interleaved with decode.
    ``page_size`` / ``n_pages`` size the shared ``PagedKVCache``;
                      ``n_pages=0`` sizes it to hold ``max_batch`` full
                      ``max_len`` rows.  ``page_size=0`` disables
                      paging (slot cache only).
    ``prefix_reuse``  share full-page common prefixes across requests.
    ``high_watermark`` / ``low_watermark``
                      pool-occupancy hysteresis band: admission defers
                      above high, spilled requests resume below low.
    """
    max_batch: int = 4
    prefill_chunk: int = 0
    page_size: int = 16
    n_pages: int = 0
    prefix_reuse: bool = True
    high_watermark: float = 0.90
    low_watermark: float = 0.60


def paged_decode_enabled(cfg, sc: Optional[SchedulerConfig],
                         max_len: int) -> bool:
    """Would a scheduler built from ``sc`` route decode through the
    page pool for this config?  (Mirrors ``ContinuousScheduler``'s own
    gate; the engine uses it for admission-time capacity checks.)"""
    sc = sc or SchedulerConfig()
    return bool(
        sc.page_size
        and getattr(cfg, "has_attention", True)
        and getattr(cfg, "kv_cache_dtype", "auto") != "int8"
        and lm.supports_paged_decode(cfg)
        and max_len % sc.page_size == 0)


def pool_capacity(sc: Optional[SchedulerConfig], max_len: int) -> int:
    """Total pages the scheduler's pool will hold."""
    sc = sc or SchedulerConfig()
    return sc.n_pages or sc.max_batch * pages_for(max_len, sc.page_size)


class ContinuousScheduler:
    """Slot-based continuous batching over one ``Engine``.

    The scheduler borrows the engine's jitted prefill/decode functions,
    degradation policy, journal and counters; it owns the waiting
    queue, the slot table, the page pool (or the ragged slot cache for
    non-paged configs), and the spill/preempt pressure ladder.
    """

    def __init__(self, engine, config: Optional[SchedulerConfig] = None):
        from repro.serve import engine as engine_mod   # circular-safe
        self._E = engine_mod
        self.eng = engine
        self.cc = config or SchedulerConfig()
        if self.cc.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got "
                             f"{self.cc.max_batch}")
        self.waiting: deque = deque()
        self.slots: List[Optional[Any]] = [None] * self.cc.max_batch
        self.cache = None                      # ragged slot cache
        self.last_tok = np.zeros(self.cc.max_batch, np.int64)
        self.kv_lens = np.zeros(self.cc.max_batch, np.int64)
        self.step_count = 0
        self.greedy = True
        self.seed = 0
        self.t_start: Dict[int, float] = {}
        self.req_pages: Dict[int, List[int]] = {}
        self.last_step: Dict[int, int] = {}    # rid -> last decode step
        self.paused: List[int] = []            # spilled rids, spill order
        self.spilled: Dict[int, Tuple[Any, int, List[Tuple]]] = {}
        self.paged: Optional[PagedKVCache] = None
        self._pf: Optional[Tuple] = None       # chunked prefill in flight
        self._chunk_fns: Dict[int, Tuple] = {} # chunk len -> jitted pair
        self._paged_jit: Optional[Tuple] = None
        cfg = engine.cfg
        if self.cc.page_size and getattr(cfg, "has_attention", True) \
                and getattr(cfg, "kv_cache_dtype", "auto") != "int8":
            n_pages = self.cc.n_pages or (
                self.cc.max_batch
                * pages_for(engine.max_len, self.cc.page_size))
            self.paged = PagedKVCache(
                cfg, n_pages, self.cc.page_size, dtype=cfg.act_dtype,
                high_watermark=self.cc.high_watermark,
                low_watermark=self.cc.low_watermark)
        self.use_paged = bool(
            self.paged is not None and lm.supports_paged_decode(cfg)
            and engine.max_len % self.cc.page_size == 0)
        self.max_pages = (engine.max_len // self.cc.page_size
                          if self.use_paged else 0)

    # ------------------------------------------------------------------
    # Queue.
    # ------------------------------------------------------------------
    def enqueue(self, req) -> None:
        self.waiting.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self._pf is not None or self.paused
                    or any(r is not None for r in self.slots))

    def inflight(self) -> List[Any]:
        """Every request the scheduler currently owns (queued, mid-
        prefill, decoding, or spilled to host)."""
        out = [r for r in self.waiting]
        if self._pf is not None:
            out.append(self._pf[0])
        out.extend(r for r in self.slots if r is not None)
        out.extend(self.spilled[rid][0] for rid in self.paused)
        return out

    # ------------------------------------------------------------------
    # The step: admit (one prefill unit) then decode (all slots).
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One scheduler tick; returns True if any work was done."""
        did = self._admit()
        did = self._decode() or did
        return did

    def drain(self, greedy: bool = True, seed: int = 0) -> None:
        """Step until every owned request is terminal.

        A tick that makes no progress while requests are still in
        flight is a scheduler stall — a bug, not a state.  It is
        ledgered as a ``scheduler.stall`` HealthEvent and every
        stranded request is FAILED with the stall as its error, so
        nothing is ever silently left QUEUED forever.
        """
        self.greedy, self.seed = bool(greedy), int(seed)
        try:
            while self.has_work:
                if not self.step():
                    self._stall()
                    break
        finally:
            self.greedy, self.seed = True, 0

    def _stall(self) -> None:
        """No-progress tick with work owned: fail the stranded requests
        loudly instead of dropping them (satellite of PR 10)."""
        stranded = [r for r in self.inflight()
                    if not self._E._terminal(r.state)]
        detail = (f"no forward progress with {len(stranded)} request(s) "
                  f"in flight: rids {sorted(r.rid for r in stranded)}")
        self.eng.monitor.note("scheduler.stall", site="serve.drain",
                              step=self.step_count, detail=detail)
        err = RuntimeError(f"scheduler stalled: {detail}")
        if self._pf is not None and self._pf[3] and self.paged is not None:
            self.paged.release(self._pf[3])    # chunked-prefill reserve
        self._pf = None
        for r in stranded:
            self._fail(r, err)
        self.waiting.clear()
        for rid in list(self.paused):
            _, _, entries = self.spilled.pop(rid)
            self.paged.release(
                [e[1] for e in entries if e[0] == "resident"])
        self.paused = []
        for i, r in enumerate(self.slots):
            if r is not None:
                self._free_slot(i)

    # -- admission ------------------------------------------------------
    def _admit(self) -> bool:
        if self._pf is not None:
            with trace.span("serve.admit", rid=self._pf[0].rid):
                return self._advance_chunked()
        did = self._try_resume()
        if self.paused:
            # spilled requests resume before anyone new is admitted:
            # admitting into the pool they are waiting on would thrash
            return did
        while self.waiting:
            free = [i for i, r in enumerate(self.slots) if r is None]
            if not free:
                return did
            req = self.waiting[0]
            if req.state != self._E.RequestState.QUEUED:
                self.waiting.popleft()
                continue                   # served elsewhere / stale
            plen = int(req.prompt.shape[0])
            chunked = bool(self.cc.prefill_chunk
                           and plen > self.cc.prefill_chunk)
            pages: Optional[List[int]] = None
            reuse: List[int] = []
            covered = 0
            if self.use_paged:
                # a request whose full KV reach exceeds the pool can
                # never complete: admitting it would livelock the
                # ladder (grow -> fail -> preempt -> recompute -> grow)
                reach = min(plen + req.max_new_tokens, self.eng.max_len)
                need_reach = pages_for(reach, self.cc.page_size)
                if need_reach > self.paged.n_pages:
                    self.waiting.popleft()
                    self._fail(req, RuntimeError(
                        f"page pool cannot hold request: kv reach "
                        f"{reach} needs {need_reach} pages, pool holds "
                        f"{self.paged.n_pages}"))
                    return True
                holders = bool(self.req_pages) or bool(self.spilled)
                if holders and self.paged.above_high():
                    self._defer(req, f"pool above high watermark "
                                     f"(occupancy "
                                     f"{self.paged.occupancy():.2f} >= "
                                     f"{self.paged.high_watermark:.2f})")
                    return did
                if not chunked and self.cc.prefix_reuse:
                    reuse, covered = self.paged.lookup_prefix(
                        np.asarray(req.prompt, np.int32))
                need = pages_for(plen, self.cc.page_size) - len(reuse)
                new = self.paged.alloc(need)
                if new is None:
                    if reuse:
                        self.paged.release(reuse)
                    if holders:
                        self._defer(req, f"page pool exhausted ({need} "
                                         f"pages needed, "
                                         f"{self.paged.free_pages} free)")
                        return did
                    self.waiting.popleft()
                    self._fail(req, RuntimeError(
                        f"page pool cannot hold prompt: {need} pages "
                        f"needed, pool holds {self.paged.n_pages}"))
                    return True
                pages = list(reuse) + new
            self.waiting.popleft()
            req.queue_reason = None
            with trace.span("serve.admit", rid=req.rid):
                self._ensure_cache()
                admitted = self.t_start.setdefault(req.rid, time.monotonic())
                if req.admitted_s is None:
                    req.admitted_s = admitted
                self.eng._warm_autotune(1, plen)
                if chunked:
                    self._pf = (req, None, 0, pages)
                    return self._advance_chunked()
                return self._prefill_whole(req, free[0], pages=pages,
                                           reuse=reuse, covered=covered)
        return did

    def _defer(self, req, reason: str) -> None:
        """Backpressure: leave ``req`` QUEUED with an explicit reason —
        the never-silent half of the admission contract."""
        if getattr(req, "queue_reason", None) != reason:
            req.queue_reason = reason
            self.eng._counters["backpressure"] += 1
            self.eng.monitor.note(
                "backpressure", site="serve.admit", step=self.step_count,
                detail=f"rid {req.rid}: {reason}")

    def _try_resume(self) -> bool:
        """Un-spill the oldest paused request once a slot is free and
        the pool is below the low watermark (or nothing is active)."""
        if not self.paused:
            return False
        free = [i for i, r in enumerate(self.slots) if r is None]
        if not free:
            return False
        if any(r is not None for r in self.slots) \
                and not self.paged.below_low():
            return False
        rid = self.paused[0]
        with trace.span("serve.admit", rid=rid):
            return self._resume(rid, free[0])

    def _resume(self, rid: int, slot: int) -> bool:
        req, kv_len, entries = self.spilled[rid]
        while True:
            pages = self.paged.unspill(entries)
            if pages is not None:
                break
            if self._preempt_youngest(exclude_rid=rid):
                continue
            # cannot make room even with everyone else gone: recompute
            # this request instead of round-tripping its pages
            self.paused.pop(0)
            del self.spilled[rid]
            self.paged.release(
                [e[1] for e in entries if e[0] == "resident"])
            self._requeue(req)
            return True
        self.paused.pop(0)
        del self.spilled[rid]
        req.state = self._E.RequestState.DECODING
        self.slots[slot] = req
        self.req_pages[rid] = pages
        self.kv_lens[slot] = kv_len
        self.last_tok[slot] = req.out_tokens[-1]
        self.last_step[rid] = self.step_count
        self.eng._counters["unspills"] += 1
        self.eng.monitor.note(
            "unspill", site="serve.admit", step=self.step_count,
            detail=f"rid {rid}: {len(pages)} pages back on device at "
                   f"kv_len {kv_len}")
        return True

    def _ensure_cache(self) -> None:
        if self.use_paged:
            return                         # the pool IS the datapath
        if self.cache is None:
            self.cache = lm.init_cache(
                self.eng.cfg, self.cc.max_batch, self.eng.max_len,
                dtype=self.eng.cfg.act_dtype)
            self.cache["index"] = jnp.zeros((self.cc.max_batch,),
                                            jnp.int32)

    def _prefill_whole(self, req, slot: int,
                       pages: Optional[List[int]] = None,
                       reuse: Optional[List[int]] = None,
                       covered: int = 0) -> bool:
        """Single-shot prefill through the engine's own jitted function
        (B=1), then install the row into ``slot``.

        On the paged datapath ``pages`` (and the ``reuse``/``covered``
        prefix share) were acquired by ``_admit`` before the request
        left the queue — allocation failure surfaces as backpressure
        there, never as a silent fallback here."""
        RequestState = self._E.RequestState
        prompt = np.asarray(req.prompt, np.int32)
        plen = len(prompt)
        if pages is None:
            reuse, covered = [], 0
            if self.paged is not None and self.cc.prefix_reuse:
                reuse, covered = self.paged.lookup_prefix(prompt)
        req.state = RequestState.PREFILLING
        dev = jnp.asarray(prompt[None])
        try:
            with trace.span("serve.prefill", rid=req.rid, plen=plen):
                if covered:
                    logits, rcache = self._prefill_from_pages(
                        prompt, reuse, covered)
                else:
                    logits, rcache, path = self.eng._execute(
                        "serve.prefill", self.step_count,
                        lambda: self.eng._prefill(self.eng.params, dev),
                        lambda: self.eng._prefill_degraded(
                            self.eng.params, dev))
                    if path == "degraded":
                        self.eng._counters["degraded_steps"] += 1
        except self._E.StepFailed as e:
            self._fail(req, e)
            if pages is not None:
                self.paged.release(pages)
            elif reuse:
                self.paged.release(reuse)
            return True
        self._store_pages(req, prompt, reuse, covered, rcache,
                          pages=pages)
        self._install(req, slot, rcache, plen, logits[0])
        return True

    def _prefill_from_pages(self, prompt, reuse: List[int],
                            covered: int):
        """Seed a fresh cache row from reused prefix pages, then prefill
        only the uncovered tail via ``lm.prefill_chunk``."""
        kp, vp = self.paged.gather(reuse)     # (L, n_kv, covered.., Dh)
        rcache = lm.init_cache(self.eng.cfg, 1, self.eng.max_len,
                               dtype=self.eng.cfg.act_dtype)
        rcache["k"] = rcache["k"].at[:, 0, :, :covered].set(
            kp[:, :, :covered].astype(rcache["k"].dtype))
        rcache["v"] = rcache["v"].at[:, 0, :, :covered].set(
            vp[:, :, :covered].astype(rcache["v"].dtype))
        rcache["index"] = jnp.asarray(covered, jnp.int32)
        tail = jnp.asarray(np.asarray(prompt[covered:], np.int32)[None])
        primary, degraded = self._chunk_fn(int(tail.shape[1]))
        start = jnp.asarray(covered, jnp.int32)
        logits, rcache, path = self.eng._execute(
            "serve.prefill", self.step_count,
            lambda: primary(self.eng.params, rcache, tail, start),
            lambda: degraded(self.eng.params, rcache, tail, start))
        if path == "degraded":
            self.eng._counters["degraded_steps"] += 1
        return logits, rcache

    def _advance_chunked(self) -> bool:
        """Push one chunk of the in-flight long prompt; on the final
        chunk, install the finished row into a free slot.

        Deadlines are checked at every chunk boundary (satellite of
        PR 10): a prompt that blows its deadline mid-prefill is evicted
        there instead of burning the remaining chunks first."""
        RequestState = self._E.RequestState
        req, rcache, pos, pages = self._pf
        prompt = np.asarray(req.prompt, np.int32)
        plen = len(prompt)
        dl = req.deadline_s
        if dl is not None \
                and time.monotonic() - self.t_start[req.rid] > dl:
            self._pf = None
            if pages:
                self.paged.release(pages)
            req.state = RequestState.EVICTED
            req.error = (f"deadline {dl:.3f}s exceeded during chunked "
                         f"prefill at position {pos}/{plen}")
            self.eng._counters["evicted"] += 1
            self.eng.monitor.note("evicted", site="serve.prefill",
                                  step=self.step_count, detail=req.error)
            self.eng._journal_terminal(req, self.step_count)
            return True
        end = min(pos + self.cc.prefill_chunk, plen)
        toks = jnp.asarray(prompt[None, pos:end])
        req.state = RequestState.PREFILLING
        try:
            if rcache is None:
                rcache = lm.init_cache(self.eng.cfg, 1, self.eng.max_len,
                                       dtype=self.eng.cfg.act_dtype)
            primary, degraded = self._chunk_fn(int(toks.shape[1]))
            start = jnp.asarray(pos, jnp.int32)
            with trace.span("serve.prefill", rid=req.rid, plen=plen):
                logits, rcache, path = self.eng._execute(
                    "serve.prefill", self.step_count,
                    lambda: primary(self.eng.params, rcache, toks, start),
                    lambda: degraded(self.eng.params, rcache, toks, start))
            if path == "degraded":
                self.eng._counters["degraded_steps"] += 1
        except self._E.StepFailed as e:
            self._pf = None
            self._fail(req, e)
            if pages:
                self.paged.release(pages)
            return True
        if end < plen:
            self._pf = (req, rcache, end, pages)
            return True
        self._pf = None
        free = [i for i, r in enumerate(self.slots) if r is None]
        self._store_pages(req, prompt, [], 0, rcache, pages=pages)
        self._install(req, free[0], rcache, plen, logits[0])
        return True

    def _chunk_fn(self, chunk_len: int) -> Tuple:
        """Jitted ``prefill_chunk`` (+ degraded XLA twin) per chunk
        length; ``start`` is traced so one trace serves every offset."""
        fns = self._chunk_fns.get(chunk_len)
        if fns is not None:
            return fns
        cfg = self.eng.cfg

        def _chunk(params, cache, toks, start):
            return lm.prefill_chunk(params, cache, toks, cfg, start)

        def _chunk_xla(params, cache, toks, start):
            with layers.forced_backend("xla"):
                return lm.prefill_chunk(params, cache, toks, cfg, start)

        fns = (jax.jit(_chunk), jax.jit(_chunk_xla))
        self._chunk_fns[chunk_len] = fns
        return fns

    def _paged_fns(self) -> Tuple:
        """Jitted ``paged_decode_step`` (+ degraded XLA twin)."""
        if self._paged_jit is None:
            cfg = self.eng.cfg

            def _step(params, kp, vp, toks, tables, kv, wpid, woff):
                return lm.paged_decode_step(params, kp, vp, toks, tables,
                                            kv, wpid, woff, cfg)

            def _step_xla(params, kp, vp, toks, tables, kv, wpid, woff):
                with layers.forced_backend("xla"):
                    return lm.paged_decode_step(params, kp, vp, toks,
                                                tables, kv, wpid, woff,
                                                cfg)

            self._paged_jit = (jax.jit(_step), jax.jit(_step_xla))
        return self._paged_jit

    def _store_pages(self, req, prompt, reuse: List[int], covered: int,
                     rcache, pages: Optional[List[int]] = None) -> None:
        """Scatter the prefilled row into the page pool.

        Paged datapath: ``pages`` were pre-acquired at admission —
        storing cannot fail.  Legacy slot-cache configs keep the
        best-effort behavior (pool exhaustion falls back to
        slot-cache-only, the pool is just a prefix-sharing mirror)."""
        if self.paged is None or "k" not in rcache:
            return
        plen = len(prompt)
        if pages is None:
            new = self.paged.alloc(
                pages_for(plen, self.cc.page_size) - len(reuse))
            if new is None:
                if reuse:
                    self.paged.release(reuse)
                return
            pages = list(reuse) + new
        with trace.span("serve.store", rid=req.rid,
                        pages=len(pages) - covered // self.cc.page_size):
            self.paged.store(prompt, pages, covered,
                             rcache["k"][:, 0], rcache["v"][:, 0])
        self.req_pages[req.rid] = pages

    def _install(self, req, slot: int, rcache, plen: int,
                 first_logits) -> None:
        """Mark the row live (paged: set its kv length; slot-cache:
        copy the B=1 prefilled row in) and emit the prompt's first
        generated token."""
        if self.use_paged:
            self.kv_lens[slot] = plen
        else:
            for key, arr in self.cache.items():
                if key == "index":
                    continue
                self.cache[key] = arr.at[:, slot].set(
                    rcache[key][:, 0].astype(arr.dtype))
            self.cache["index"] = self.cache["index"].at[slot].set(plen)
        req.state = self._E.RequestState.DECODING
        self.slots[slot] = req
        with trace.span("serve.emit", rows=1):
            self._emit(slot, first_logits)

    # -- the pressure ladder --------------------------------------------
    def _acquire_decode_page(self, slot: int) -> bool:
        """Attach one more page to ``slot``'s request, running the
        pressure ladder on allocation failure: spill the coldest other
        active request, then preempt the youngest other holder.
        Returns False only when the ladder is exhausted (the caller
        preempts the needy request itself)."""
        req = self.slots[slot]
        while True:
            new = self.paged.alloc(1)
            if new is not None:
                self.req_pages[req.rid].extend(new)
                return True
            if self._spill_coldest(exclude_slot=slot):
                continue
            if self._preempt_youngest(exclude_rid=req.rid):
                continue
            return False

    def _spill_coldest(self, exclude_slot: int) -> bool:
        """Spill the LRU active request (smallest last decode step,
        ties broken toward the youngest rid) other than
        ``exclude_slot``.  Returns True if a victim moved to host."""
        cands = [i for i, r in enumerate(self.slots)
                 if r is not None and i != exclude_slot]
        if not cands:
            return False
        victim = min(cands, key=lambda i: (
            self.last_step.get(self.slots[i].rid, 0),
            -self.slots[i].rid))
        return self._spill_slot(victim)

    def _spill_slot(self, slot: int) -> bool:
        """Move ``slot``'s request to the host spill tier and park it
        in ``paused``.  An injected ``pool.spill`` failure aborts the
        spill (the caller escalates to preemption)."""
        req = self.slots[slot]
        pages = self.req_pages[req.rid]
        try:
            entries = self.paged.spill(pages)
        except health.SimulatedFailure as e:
            self.eng.monitor.note(
                "spill-failed", site="pool.spill", step=self.step_count,
                detail=f"rid {req.rid}: {e}")
            return False
        del self.req_pages[req.rid]
        n_host = sum(1 for e in entries if e[0] == "host")
        self.spilled[req.rid] = (req, int(self.kv_lens[slot]), entries)
        self.paused.append(req.rid)
        self.slots[slot] = None
        self.last_tok[slot] = 0
        self.kv_lens[slot] = 0
        self.eng._counters["spills"] += 1
        self.eng._counters["spilled_pages"] += n_host
        self.eng.monitor.note(
            "spill", site="serve.decode_step", step=self.step_count,
            detail=f"rid {req.rid}: {n_host} page(s) to host "
                   f"({len(entries) - n_host} shared stay pinned)")
        return True

    def _preempt_youngest(self, exclude_rid: Optional[int] = None
                          ) -> bool:
        """Preempt the youngest (highest-rid) request holding pool
        pages — paused before active, so recompute cost lands on the
        request with the least standing work.  Returns True if one was
        preempted."""
        paused = [rid for rid in self.paused if rid != exclude_rid]
        if paused:
            rid = max(paused)
            req, _, entries = self.spilled.pop(rid)
            self.paused.remove(rid)
            self.paged.release(
                [e[1] for e in entries if e[0] == "resident"])
            self._requeue(req)
            return True
        cands = [i for i, r in enumerate(self.slots)
                 if r is not None and r.rid != exclude_rid]
        if not cands:
            return False
        slot = max(cands, key=lambda i: self.slots[i].rid)
        self._preempt_slot(slot)
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Release ``slot``'s pages and re-queue its request."""
        req = self.slots[slot]
        self.paged.release(self.req_pages.pop(req.rid))
        self.slots[slot] = None
        self.last_tok[slot] = 0
        self.kv_lens[slot] = 0
        self.last_step.pop(req.rid, None)
        self._requeue(req)

    def _requeue(self, req) -> None:
        """The preemption tail: journal a fsync'd ``preempt`` record,
        stash the emitted tokens as replay expectations (the
        deterministic recompute must reproduce them bit-exactly —
        ``replay_divergence`` fires if it does not), and put the
        request back at the head of the queue."""
        if self.eng.journal is not None:
            self.eng.journal.append(
                "preempt", fsync=True, rid=req.rid, step=self.step_count,
                tokens_done=len(req.out_tokens))
        if req.out_tokens:
            exp = self.eng._replay_expected
            if len(req.out_tokens) > len(exp.get(req.rid, [])):
                exp[req.rid] = list(req.out_tokens)
        req.out_tokens = []
        req.state = self._E.RequestState.QUEUED
        self.waiting.appendleft(req)
        self.eng._counters["preemptions"] += 1
        self.eng.monitor.note(
            "preempt", site="serve.decode_step", step=self.step_count,
            detail=f"rid {req.rid} re-queued under memory pressure "
                   f"(will recompute deterministically)")

    # -- decode ---------------------------------------------------------
    def _sweep_deadlines(self) -> bool:
        """Evict every active or spilled request past its deadline."""
        now = time.monotonic()
        evicted = False
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            dl = r.deadline_s
            if dl is not None and now - self.t_start[r.rid] > dl:
                self._evict(r, i)
                evicted = True
        for rid in list(self.paused):
            req, _, entries = self.spilled[rid]
            dl = req.deadline_s
            if dl is not None and now - self.t_start.get(rid, now) > dl:
                self.paused.remove(rid)
                del self.spilled[rid]
                self.paged.release(
                    [e[1] for e in entries if e[0] == "resident"])
                self._evict(req, None)
                evicted = True
        return evicted

    def _evict(self, r, slot: Optional[int]) -> None:
        r.state = self._E.RequestState.EVICTED
        r.error = (f"deadline {r.deadline_s:.3f}s exceeded after "
                   f"{len(r.out_tokens)} tokens")
        self.eng._counters["evicted"] += 1
        self.eng.monitor.note("evicted", site="serve.decode_step",
                              step=self.step_count, detail=r.error)
        self.eng._journal_terminal(r, self.step_count)
        if slot is not None:
            self._free_slot(slot)
        else:
            self.t_start.pop(r.rid, None)
            self.last_step.pop(r.rid, None)

    def _decode(self) -> bool:
        """One decode step over every occupied slot, inside the
        ``serve.decode`` span; its length is the step the health monitor
        records."""
        with trace.span("serve.decode") as sp:
            did, rows = (self._decode_paged() if self.use_paged
                         else self._decode_slots())
            sp.set(rows=rows)
        if rows:
            self.eng.monitor.record(self.step_count, sp.seconds)
        return did

    def _decode_slots(self) -> Tuple[bool, int]:
        """Decode off the contiguous slot cache; returns (work done, rows
        decoded)."""
        with trace.span("serve.prepare"):
            evicted = self._sweep_deadlines()
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if not active:
                return evicted, 0
            self.step_count += 1
            toks = jnp.asarray(self.last_tok[:, None].astype(np.int32))
        cache = self.cache
        try:
            logits, cache, path = self.eng._execute(
                "serve.decode_step", self.step_count,
                lambda: self.eng._decode(self.eng.params, cache, toks),
                lambda: self.eng._decode_degraded(self.eng.params, cache,
                                                  toks))
        except self._E.StepFailed as e:
            for i in active:
                self._fail(self.slots[i], e)
                self._free_slot(i)
            return True, 0
        self.cache = cache
        if path == "degraded":
            self.eng._counters["degraded_steps"] += 1
            for i in active:
                self.slots[i].degraded_steps += 1
        self._emit_rows(active, logits)
        # park freed rows at index 0 so the cache state is a pure
        # function of the live requests (deterministic replay)
        occupied = np.asarray(
            [r is not None for r in self.slots], bool)
        self.cache["index"] = jnp.where(
            jnp.asarray(occupied), self.cache["index"], 0)
        return True, len(active)

    def _decode_paged(self) -> Tuple[bool, int]:
        """One decode step straight off the page pool: grow rows at
        page boundaries (running the pressure ladder on failure), then
        dispatch ``lm.paged_decode_step`` over the block tables.
        Returns (work done, rows decoded)."""
        with trace.span("serve.prepare"):
            evicted = self._sweep_deadlines()
            if not any(r is not None for r in self.slots):
                return evicted, 0
            ps = self.cc.page_size
            # page-boundary growth; the ladder may spill/preempt *other*
            # slots while satisfying row i, so re-check liveness as we go
            for i in range(self.cc.max_batch):
                req = self.slots[i]
                if req is None:
                    continue
                if int(self.kv_lens[i]) // ps < len(
                        self.req_pages[req.rid]):
                    continue
                if not self._acquire_decode_page(i):
                    # ladder exhausted with the needy request the only
                    # holder left: recompute it later instead of wedging
                    self._preempt_slot(i)
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if not active:
                return True, 0             # the ladder did the work
            self.step_count += 1
            mb = self.cc.max_batch
            tables = np.zeros((mb, self.max_pages), np.int32)
            wp = np.full(mb, self.paged.scratch, np.int32)
            wo = np.zeros(mb, np.int32)
            for i in active:
                pages = self.req_pages[self.slots[i].rid]
                tables[i, :len(pages)] = pages
                kv = int(self.kv_lens[i])
                wp[i] = pages[kv // ps]
                wo[i] = kv % ps
            toks = jnp.asarray(self.last_tok[:, None].astype(np.int32))
            tables_d = jnp.asarray(tables)
            kv_d = jnp.asarray(self.kv_lens.astype(np.int32))
            wp_d, wo_d = jnp.asarray(wp), jnp.asarray(wo)
        k_pool, v_pool = self.paged.k_pages, self.paged.v_pages
        primary, degraded = self._paged_fns()
        try:
            logits, pools, path = self.eng._execute(
                "serve.decode_step", self.step_count,
                lambda: primary(self.eng.params, k_pool, v_pool, toks,
                                tables_d, kv_d, wp_d, wo_d),
                lambda: degraded(self.eng.params, k_pool, v_pool, toks,
                                 tables_d, kv_d, wp_d, wo_d))
        except self._E.StepFailed as e:
            for i in active:
                self._fail(self.slots[i], e)
                self._free_slot(i)
            return True, 0
        # commit the pools only on step success — same pre-step-cache
        # retry contract as the slot path
        self.paged.k_pages, self.paged.v_pages = pools
        if path == "degraded":
            self.eng._counters["degraded_steps"] += 1
            for i in active:
                self.slots[i].degraded_steps += 1
        for i in active:
            self.kv_lens[i] += 1           # before _emit: it may free
        self._emit_rows(active, logits)
        return True, len(active)

    def _emit_rows(self, active: List[int], logits) -> None:
        """Bring the step's logits to the host and emit one token per
        decoded row."""
        with trace.span("serve.fetch"):
            logits_np = np.asarray(logits)
        with trace.span("serve.emit", rows=len(active)):
            for i in active:
                self._emit(i, logits_np[i])

    def _emit(self, slot: int, logits_row) -> None:
        """Sample one token for ``slot``, journal it, finish on budget."""
        RequestState = self._E.RequestState
        req = self.slots[slot]
        sp = getattr(req, "sampling", None)
        greedy = self.greedy if sp is None else sp.greedy
        if greedy:
            t = int(np.argmax(np.asarray(logits_row)))
        else:
            seed = self.seed if sp is None else sp.seed
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(seed), req.rid),
                len(req.out_tokens))
            t = int(jax.random.categorical(
                key, jnp.asarray(logits_row)))
        req.out_tokens.append(t)
        self.last_tok[slot] = t
        self.last_step[req.rid] = self.step_count
        if self.eng.journal is not None:
            self.eng.journal.append("token", rid=req.rid,
                                    step=len(req.out_tokens), token=t)
        if len(req.out_tokens) >= req.max_new_tokens:
            req.state = RequestState.DONE
            self.eng._counters["completed"] += 1
            self.eng._journal_terminal(req, self.step_count)
            self._free_slot(slot)

    # -- bookkeeping ----------------------------------------------------
    def _fail(self, req, err: BaseException) -> None:
        req.state = self._E.RequestState.FAILED
        req.error = str(err)
        self.eng._counters["failed"] += 1
        self.eng._journal_terminal(req, self.step_count)
        pages = self.req_pages.pop(req.rid, None)
        if pages is not None:
            self.paged.release(pages)

    def _free_slot(self, slot: int) -> None:
        req = self.slots[slot]
        self.slots[slot] = None
        self.last_tok[slot] = 0
        self.kv_lens[slot] = 0
        self.t_start.pop(req.rid, None)
        self.last_step.pop(req.rid, None)
        pages = self.req_pages.pop(req.rid, None)
        if pages is not None:
            self.paged.release(pages)

    def report(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "steps": self.step_count,
            "waiting": len(self.waiting),
            "active": sum(r is not None for r in self.slots),
            "paused": len(self.paused),
            "max_batch": self.cc.max_batch,
            "paged_decode": self.use_paged,
        }
        if self.paged is not None:
            out["pages"] = self.paged.report()
        return out
