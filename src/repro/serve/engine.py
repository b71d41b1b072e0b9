"""Serving engine: batched prefill + decode with a request lifecycle.

``make_serve_step`` builds the one-token decode function the dry-run
lowers for the decode_32k / long_500k cells; ``Engine`` batches
requests, prefills, and streams tokens — now behind a fault-tolerant
request lifecycle:

    QUEUED -> PREFILLING -> DECODING -> {DONE, FAILED, EVICTED}

``submit`` is the admission gate: it validates prompts (empty, over
``max_len``, non-integer dtype -> ``ValueError``) and rejects requests
whose decode-step attention footprint cannot fit the hardware's VMEM
under *any* dataflow the explorer can enumerate (``AdmissionError``).
``serve`` drives admitted requests through prefill and the decode loop;
every step runs under ``_execute``:

  * the ``serve.prefill`` / ``serve.decode_step`` fault-injection sites
    (``runtime.health.maybe_inject``) fire here, so drills exercise the
    exact retry path real failures take;
  * a non-finite sentinel checks the step's logits on the host — a NaN
    or Inf (bad kernel output, injected ``nan`` fault) counts as a step
    failure just like a raised lowering error;
  * on failure the ``DegradationPolicy`` demotes to the ``backend=
    "xla"`` escape hatch (``layers.forced_backend``) and the step is
    retried with exponential backoff against the *pre-step* cache —
    JAX's functional caches make commit-after-validate free, so a
    poisoned step never contaminates later tokens;
  * after ``cooldown_steps`` the policy re-probes the primary path.

Per-request deadlines evict slow requests (EVICTED) instead of stalling
the batch; ``max_new_tokens`` budgets are clamped to the cache capacity
(``max_len``).  ``stats()`` reports admission/backpressure counters next
to the ``HealthMonitor`` ledger, so demotions, retries, stragglers and
injected faults surface in one place.

Crash safety (PR 7) extends no-request-*fails* to no-request-is-*lost*:

  * every admission, emitted token and terminal transition is written
    ahead to a durable ``RequestJournal`` (serve/journal.py) when the
    engine is given a journal directory (``journal_dir=`` or
    ``REPRO_JOURNAL_DIR``);
  * ``snapshot()`` persists the full engine state — request table,
    emitted tokens, counters, health ledger, KV cache, last logits and
    params — through ``ckpt.Checkpointer``, on a decode-step cadence
    (``snapshot_every=`` / ``REPRO_SNAPSHOT_EVERY``);
  * after a kill, a fresh engine's ``restore()`` rebuilds the request
    table from the journal, loads the newest intact snapshot (falling
    back across corrupt ones, then to journal-only cold replay), and
    re-admits in-flight requests at their exact decode position; the
    next ``serve()`` call continues the decode loop from the restored
    pre-step cache.  Greedy decode is a pure function of params + the
    journaled prompts, so the recovered token streams are bit-identical
    to the uninterrupted run — the crash-drill CI job SIGKILLs the loop
    at journaled steps and asserts exactly that.  ``restore()`` accepts
    a ``devices=`` survivor list and reshards the snapshot through
    ``runtime.elastic.plan_remesh``, so recovery works onto a smaller
    mesh than the one that crashed.

Continuous batching (PR 8) lifts the equal-prompt-length restriction:

  * ``submit()`` now returns a ``RequestHandle`` — still a ``Request``
    (every existing call site keeps working) plus a ``tokens()``
    stream iterator and a blocking ``result()``, both of which drive
    the engine's continuous scheduler (``serve/scheduler.py``) one
    step at a time;
  * ``serve()`` on a mixed-prompt-length batch no longer raises — it
    routes through the scheduler: per-step admission into a fixed pool
    of cache slots, per-row banded decode (vector ``kv_len``), chunked
    prefill interleaved with decode, and prefix-page reuse on the
    shared ``PagedKVCache``.  Equal-length batches keep the original
    batch-synchronous loop (and its snapshot/warm-resume path)
    bit-for-bit;
  * ``step()`` / ``drain()`` expose the scheduler directly;
    ``generate()`` remains as a deprecated shim over submit + drain.
  * crash safety composes: continuous serving journals the same
    submit/serve/token/terminal records (``mode="continuous"``), and a
    cold ``restore()`` replays the ragged batch through a fresh
    scheduler — admission order, slot assignment and the fixed-shape
    ragged cache are all deterministic, so recovered greedy streams
    stay bit-identical (the ragged crash drill pins this).

Memory-pressure resilience (PR 10) makes the page pool the continuous
path's real decode datapath and makes it pressure-proof:

  * for paged-decode-capable configs the scheduler routes every decode
    step through ``ops.paged_attention`` off the block tables — no
    contiguous slot cache — so pool occupancy is the true capacity
    signal, and ``submit()`` additionally rejects requests whose KV
    reach cannot fit the pool at all (``AdmissionError``);
  * under pressure the scheduler runs an explicit ladder — watermark
    admission backpressure (queued-with-reason via
    ``Request.queue_reason``, never silent), host spill of the coldest
    request's pages (``PagedKVCache.spill``/``unspill``, shared prefix
    pages stay pinned), then preemption of the youngest request
    (fsync'd ``preempt`` journal record, deterministic
    recompute-on-resume verified by ``replay_divergence``);
  * ``stats()`` surfaces ``spills`` / ``spilled_pages`` / ``unspills``
    / ``preemptions`` / ``backpressure`` counters plus the scheduler's
    pool report (occupancy, watermark state), and the ``pool.alloc`` /
    ``pool.spill`` fault sites make the whole ladder drillable —
    including SIGKILL mid-spill, which recovers via the PR-7 journal
    with zero lost or duplicated requests.
"""
from __future__ import annotations

import dataclasses
import enum
import os
import time
import warnings
from typing import (Any, Callable, Dict, Iterator, List, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import Checkpointer, CheckpointError
from repro.core import autotune, cost_model, explorer
from repro.models import layers, lm
from repro.runtime import elastic, health, trace
from repro.serve import journal as journal_lib
from repro.serve.paged_cache import pages_for
from repro.serve.scheduler import (ContinuousScheduler, SamplingParams,
                                   SchedulerConfig, paged_decode_enabled,
                                   pool_capacity)

health.register_site("snapshot.save")
health.register_site("engine.restore")


def make_serve_step(cfg, dist: Optional[lm.Dist] = None,
                    unroll: int = 1) -> Callable:
    """decode one token for the whole batch.

    serve_step(params, cache, tokens (B,1)) -> (logits (B,V), cache)
    """

    def serve_step(params, cache, tokens):
        return lm.decode_step(params, cache, tokens, cfg, dist=dist,
                              unroll=unroll)

    return serve_step


def make_prefill_fn(cfg, dist: Optional[lm.Dist] = None) -> Callable:
    def prefill_fn(params, tokens, enc_frames=None):
        return lm.prefill(params, tokens, cfg, max_len=None,
                          enc_frames=enc_frames, dist=dist)

    return prefill_fn


class RequestState(str, enum.Enum):
    QUEUED = "queued"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    DONE = "done"
    FAILED = "failed"
    EVICTED = "evicted"


_TERMINAL = ("done", "failed", "evicted")


def _terminal(state: "RequestState") -> bool:
    return state.value in _TERMINAL


def to_state_safe(value) -> "RequestState":
    """RequestState from a journal/snapshot string; QUEUED on junk."""
    try:
        return RequestState(value)
    except ValueError:
        return RequestState.QUEUED


class AdmissionError(ValueError):
    """Request rejected at admission (resource infeasibility)."""


class StepFailed(RuntimeError):
    """A prefill/decode step failed on both kernel paths, retries
    exhausted — the requests it was serving transition to FAILED."""


class NonFiniteLogits(RuntimeError):
    """The post-step sentinel saw NaN/Inf logits."""


@dataclasses.dataclass
class Request:
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int
    deadline_s: Optional[float] = None   # wall-clock budget from serve start
    rid: int = -1
    state: RequestState = RequestState.QUEUED
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    degraded_steps: int = 0       # decode steps served on the XLA path
    queue_reason: Optional[str] = None   # why a QUEUED request is waiting
    #                                      (watermark / pool backpressure)
    admitted_s: Optional[float] = None   # monotonic time of its first
    #                                      admission to a batch row


@dataclasses.dataclass
class RequestHandle(Request):
    """What ``Engine.submit`` returns: a ``Request`` (so every existing
    consumer of the request table keeps working) bound to its engine,
    with a token-stream view over the continuous scheduler.

    ``tokens()`` yields generated token ids as they land, stepping the
    engine's scheduler whenever the stream runs dry; ``result()``
    drains the stream and returns the full output (raising
    ``StepFailed`` if the request ended FAILED).  Handles served
    through the batch-synchronous ``Engine.serve`` path work too —
    their tokens are already in ``out_tokens`` by the time the stream
    is read.
    """
    sampling: Optional[SamplingParams] = None
    engine: Optional["Engine"] = dataclasses.field(
        default=None, repr=False, compare=False)

    def tokens(self) -> Iterator[int]:
        i = 0
        while True:
            while i < len(self.out_tokens):
                yield self.out_tokens[i]
                i += 1
            if _terminal(self.state):
                return
            if self.engine is None:
                raise RuntimeError(
                    f"request {self.rid} is detached from its engine "
                    f"and not terminal; cannot stream")
            self.engine.step()

    def result(self) -> np.ndarray:
        """Block until terminal; the generated tokens as (n,) int32."""
        for _ in self.tokens():
            pass
        if self.state == RequestState.FAILED:
            raise StepFailed(
                f"request {self.rid} ended failed: {self.error}")
        return np.asarray(self.out_tokens, np.int32)


class Engine:
    """Batched serving loop with admission, degradation and retries.

    Equal-prompt-length batches run the original batch-synchronous
    loop (prefill once, decode until the last request finishes);
    mixed-length batches — and the ``step()``/``drain()``/handle
    streaming API — run the continuous scheduler: per-step admission
    into cache slots, per-row banded decode, chunked prefill and
    prefix-page reuse (``serve/scheduler.py``).  ``generate`` is kept
    as a deprecated prompts-in/tokens-out shim over submit + drain.

    ``hw`` is the admission-control hardware model (VMEM feasibility of
    the decode-step attention), by default the device's
    (``cost_model.hardware_for``: an unknown TPU kind raises); tests
    pass a tiny ``HardwareSpec`` to force rejections.
    ``policy``/``monitor`` own degradation state and the health ledger;
    callers may share one monitor across engines.
    """

    def __init__(self, cfg, params, max_len: int = 2048,
                 dist: Optional[lm.Dist] = None,
                 monitor: Optional[health.HealthMonitor] = None,
                 policy: Optional[health.DegradationPolicy] = None,
                 hw: Optional[cost_model.HardwareSpec] = None,
                 validate_outputs: bool = True,
                 journal_dir: Optional[str] = None,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: Optional[int] = None,
                 scheduler_config: Optional[SchedulerConfig] = None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.dist = dist
        self.hw = hw if hw is not None else cost_model.hardware_for()
        self.validate_outputs = validate_outputs
        self.monitor = monitor if monitor is not None else health.HealthMonitor()
        self.policy = policy if policy is not None else health.DegradationPolicy()
        jd = journal_dir or journal_lib.journal_dir()
        self.journal = journal_lib.RequestJournal(jd) if jd else None
        sd = snapshot_dir or (os.path.join(jd, "snapshots") if jd else None)
        self.snapshots = Checkpointer(sd) if sd else None
        if snapshot_every is None:
            snapshot_every = int(
                os.environ.get("REPRO_SNAPSHOT_EVERY", "0") or 0)
        self.snapshot_every = snapshot_every
        # live serve-loop state for snapshot(): (reqs, cache, logits,
        # step, greedy, seed) — valid between decode steps only
        self._live: Optional[Tuple] = None
        self._pending_resume: Optional[Dict[str, Any]] = None
        self._replay_expected: Dict[int, List[int]] = {}
        self._decode = jax.jit(make_serve_step(cfg, dist))
        self._prefill = jax.jit(
            lambda p, t: lm.prefill(p, t, cfg, max_len=max_len, dist=dist)
        )

        # Degraded twins: same computation forced through the XLA escape
        # hatch.  The context manager must be live while the function
        # body *traces*, so it wraps the body inside the jitted callee
        # rather than the jit() call.
        def _decode_xla(params, cache, tokens):
            with layers.forced_backend("xla"):
                return lm.decode_step(params, cache, tokens, cfg, dist=dist)

        def _prefill_xla(params, tokens):
            with layers.forced_backend("xla"):
                return lm.prefill(params, tokens, cfg, max_len=max_len,
                                  dist=dist)

        self._decode_degraded = jax.jit(_decode_xla)
        self._prefill_degraded = jax.jit(_prefill_xla)
        self._warmed = set()
        self._next_rid = 0
        self.scheduler_config = scheduler_config
        self._scheduler: Optional[ContinuousScheduler] = None
        self._backlog: List[RequestHandle] = []
        # (seq len, kv reach) -> feasible
        self._admission_cache: Dict[Tuple[int, int], bool] = {}
        self._counters: Dict[str, int] = {
            "submitted": 0, "admitted": 0, "rejected": 0,
            "completed": 0, "failed": 0, "evicted": 0,
            "retries": 0, "demotions": 0, "degraded_steps": 0,
            "budget_clamped": 0,
            "snapshots_saved": 0, "snapshot_errors": 0,
            "recovered": 0, "replayed_steps": 0,
            "replay_divergence": 0, "restore_fallbacks": 0,
            "spills": 0, "spilled_pages": 0, "unspills": 0,
            "preemptions": 0, "backpressure": 0, "ticks": 0,
        }

    # ------------------------------------------------------------------
    # Admission.
    # ------------------------------------------------------------------
    def _attention_feasible(self, seq: int,
                            cap: Optional[int] = None) -> bool:
        """Can every attention workload this request implies be realized
        under ``self.hw``'s VMEM by at least one explorer candidate?

        ``cap`` is the request's actual KV reach — ``prompt +
        max_new_tokens``, clamped to capacity.  Probing at ``max_len``
        regardless of the request's budget over-rejected short requests
        on small-VMEM parts (a 10-token request was billed for a
        2048-position decode it could never reach); the reach-aware
        probe admits everything the request can actually touch.
        """
        cap = int(cap if cap is not None else self.max_len)
        key = (seq, cap)
        if key in self._admission_cache:
            return self._admission_cache[key]
        ok = True
        with trace.span("serve.admission_check", seq=seq, reach=cap):
            for p in lm.hot_attention_problems(self.cfg, 1, max(seq, 1),
                                               cap):
                if not explorer.enumerate_attention_candidates(p, self.hw):
                    ok = False
                    break
        self._admission_cache[key] = ok
        return ok

    def _reject(self, reason: str, exc_type=ValueError) -> None:
        self._counters["rejected"] += 1
        self.monitor.note("admission-reject", site="serve.submit",
                          detail=reason)
        raise exc_type(reason)

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None,
               sampling: Optional[SamplingParams] = None
               ) -> RequestHandle:
        """Validate and admit one request (state QUEUED), or raise.

        Returns a ``RequestHandle``: stream its tokens with
        ``handle.tokens()`` / ``handle.result()``, or pass it (with
        others) to ``serve()`` / ``drain()``.  ``sampling`` bundles the
        per-request settings (``SamplingParams``); the explicit
        ``max_new_tokens`` / ``deadline_s`` arguments win over it.

        ``ValueError`` for malformed input (empty / over-``max_len`` /
        non-integer prompt, non-positive budget); ``AdmissionError``
        (a ``ValueError`` subclass) when the decode-step attention
        cannot fit the hardware's VMEM under any dataflow at the
        request's KV reach (``prompt + budget``, clamped to capacity).
        """
        with trace.span("serve.submit") as sp:
            req = self._submit(prompt, max_new_tokens, deadline_s, sampling)
            sp.set(rid=req.rid)
        return req

    def _submit(self, prompt, max_new_tokens: Optional[int],
                deadline_s: Optional[float],
                sampling: Optional[SamplingParams]) -> RequestHandle:
        self._counters["submitted"] += 1
        if max_new_tokens is None:
            max_new_tokens = (sampling.max_new_tokens if sampling
                              is not None else 16)
        if deadline_s is None and sampling is not None:
            deadline_s = sampling.deadline_s
        prompt = np.asarray(prompt)
        if prompt.ndim != 1:
            self._reject(f"prompt must be rank-1 (one request), got "
                         f"shape {prompt.shape}")
        if prompt.size == 0:
            self._reject("empty prompt: need at least one token")
        if not np.issubdtype(prompt.dtype, np.integer):
            self._reject(f"prompt dtype must be integer token ids, got "
                         f"{prompt.dtype}")
        plen = int(prompt.shape[0])
        if plen >= self.max_len:
            self._reject(
                f"prompt length {plen} leaves no decode room under "
                f"max_len={self.max_len}")
        if max_new_tokens < 1:
            self._reject(f"max_new_tokens must be >= 1, got "
                         f"{max_new_tokens}")
        reach = min(plen + max_new_tokens, self.max_len)
        if not self._attention_feasible(plen, reach):
            self._reject(
                f"no VMEM-feasible attention dataflow for prompt length "
                f"{plen} / kv reach {reach} (max_len={self.max_len}) on "
                f"{self.hw.name} ({self.hw.vmem_bytes} bytes VMEM)",
                AdmissionError)
        if paged_decode_enabled(self.cfg, self.scheduler_config,
                                self.max_len):
            sc = self.scheduler_config or SchedulerConfig()
            need = pages_for(reach, sc.page_size)
            cap = pool_capacity(sc, self.max_len)
            if need > cap:
                self._reject(
                    f"page pool cannot hold request: kv reach {reach} "
                    f"needs {need} pages of {sc.page_size}, pool "
                    f"capacity is {cap} pages", AdmissionError)
        budget = min(max_new_tokens, self.max_len - plen)
        if budget < max_new_tokens:
            self._counters["budget_clamped"] += 1
            self.monitor.note(
                "backpressure", site="serve.submit",
                detail=f"budget clamped {max_new_tokens} -> {budget} "
                       f"(cache capacity max_len={self.max_len})")
        self._counters["admitted"] += 1
        req = RequestHandle(prompt=np.asarray(prompt, np.int32),
                            max_new_tokens=budget, deadline_s=deadline_s,
                            rid=self._next_rid, sampling=sampling,
                            engine=self)
        self._next_rid += 1
        self._backlog.append(req)
        if self.journal is not None:
            # WAL contract: the caller is told "admitted" only after the
            # admission is durable, so a kill can never lose a request
            # the client believes is in flight
            self.journal.append(
                "submit", fsync=True, rid=req.rid,
                prompt=[int(t) for t in req.prompt],
                max_new_tokens=req.max_new_tokens,
                deadline_s=req.deadline_s)
        sched = self._scheduler
        if (sched is not None and sched.use_paged
                and sched.paged.above_high()):
            # backpressure is queued-with-reason, never a silent drop:
            # the request is admitted and durable, but the caller can
            # see it will wait for the pool to drain below the
            # watermark before it is scheduled
            req.queue_reason = (
                f"pool above high watermark (occupancy "
                f"{sched.paged.occupancy():.2f})")
            self._counters["backpressure"] += 1
            self.monitor.note("backpressure", site="serve.submit",
                              detail=f"rid {req.rid}: "
                                     f"{req.queue_reason}")
        return req

    # ------------------------------------------------------------------
    # Guarded step execution: inject -> run -> sentinel -> retry/demote.
    # ------------------------------------------------------------------
    def _execute(self, site: str, step: int, primary: Callable,
                 degraded: Callable) -> Tuple[Any, Any, str]:
        """Run one engine step fault-tolerantly.

        Picks the kernel path from the DegradationPolicy, fires the
        injection site, validates logits finiteness, and on any failure
        demotes + retries with backoff.  Returns (logits, cache, path).
        Raises ``StepFailed`` when retries are exhausted.
        """
        attempt = 0
        while True:
            path = self.policy.backend_for(step, self.monitor)
            fn = primary if path == "primary" else degraded
            try:
                fault = health.maybe_inject(site)
                logits, cache = fn()
                if fault == "nan":
                    logits = logits * jnp.asarray(jnp.nan, logits.dtype)
                with trace.span("serve.wait"):
                    jax.block_until_ready(logits)
                # the model masks the padding rows past vocab_size to
                # -inf: only the real vocabulary must be finite
                if self.validate_outputs:
                    with trace.span("serve.validate"):
                        finite = bool(jnp.all(jnp.isfinite(
                            logits[..., :self.cfg.vocab_size])))
                    if not finite:
                        raise NonFiniteLogits(
                            f"non-finite logits from {site} step {step} "
                            f"({path} path)")
                return logits, cache, path
            except Exception as e:
                # SimulatedFailure, NonFiniteLogits, kernel lowering /
                # interpret errors — anything a bad step can surface.
                # Kept without its traceback: the frames would hold the
                # step's device arguments (parameters, page pools) in a
                # reference cycle until the next garbage collection.
                failure = e.with_traceback(None)
            self.policy.on_failure(site, step, failure, self.monitor)
            self._counters["demotions"] += 1
            attempt += 1
            if attempt > self.policy.max_retries:
                raise StepFailed(
                    f"{site} step {step} failed after "
                    f"{self.policy.max_retries} retries: "
                    f"{type(failure).__name__}: {failure}") from failure
            self._counters["retries"] += 1
            self.monitor.note("retry", site=site, step=step,
                              detail=f"attempt {attempt} after "
                                     f"{type(failure).__name__}")
            time.sleep(self.policy.backoff_seconds(attempt - 1))

    # ------------------------------------------------------------------
    # Serving.
    # ------------------------------------------------------------------
    def _warm_autotune(self, batch: int, seq: int) -> None:
        """Populate the dataflow-spec cache for this request shape so the
        prefill and decode traces hit memoized specs instead of
        enumerating the explorer's candidate space.  Covers the hot GEMM
        shapes, the attention shapes the model actually serves — the
        prefill square, the ``sq=1``/``skv=max_len`` cached-decode step
        (traced valid length, keyed as the worst case), plus the
        windowed variants of both for sliding-window configs and int8
        KV-cache decode keys (``lm.hot_attention_problems``) — and, for
        configs with a conv frontend (audio family), the frontend's
        ``ConvProblem`` shapes — today the whisper frontend is stubbed
        (precomputed frame embeddings), so the conv warm-up is cheap
        forward-keying for when the real frontend lands on
        ``ops.conv2d_fused``.  ``binary_mlp`` configs additionally warm
        their prefill and decode ``BinaryProblem`` shapes.  Only runs
        when the model will actually take the Pallas kernel path."""
        if not (getattr(self.cfg, "use_pallas_kernels", False)
                and jax.default_backend() == "tpu"):
            return
        key = (batch, seq)
        if key in self._warmed:
            return
        self._warmed.add(key)
        with trace.span("serve.autotune_warm", seq=seq):
            autotune.warm(lm.hot_gemm_problems(self.cfg, batch, seq)
                          + lm.hot_gemm_problems(self.cfg, batch, 1)
                          + lm.hot_attention_problems(self.cfg, batch, seq,
                                                      self.max_len)
                          + lm.hot_conv_problems(self.cfg, batch, seq)
                          + lm.hot_binary_problems(self.cfg, batch, seq)
                          + lm.hot_binary_problems(self.cfg, batch, 1))

    def serve(self, requests: Sequence[Request], greedy: bool = True,
              seed: int = 0) -> List[Request]:
        """Drive a batch of QUEUED requests to a terminal state.

        Equal-prompt-length batches run the batch-synchronous loop
        (uniform-position cache, snapshot-resumable); mixed-length
        batches route through the continuous scheduler (per-row banded
        cache, per-step admission).  Terminal states: DONE (budget
        reached), EVICTED (deadline), FAILED (step failed beyond
        retries).  Returns the same request objects for convenience.

        After ``restore()``, serving requests that include a recovered
        in-flight batch continues that batch from its restored decode
        position — the snapshot's pre-step cache and logits when one
        was loaded, or a fresh prefill + deterministic re-decode (cold
        replay) otherwise.  The resumed loop uses the *journaled*
        greedy/seed, not this call's arguments, so replay cannot be
        skewed by a caller passing different sampling settings.
        """
        pending = self._take_resume(requests)
        mode = "batch"
        if pending is not None:
            greedy, seed = pending["greedy"], pending["seed"]
            mode = pending.get("mode", "batch")
            reqs = pending["reqs"]
            if pending["cache"] is not None:
                # warm restart: decode continues on the snapshot cache
                self._decode_loop(reqs, pending["cache"],
                                  pending["logits"], pending["step"],
                                  time.monotonic(), greedy, seed)
                self._check_replay(requests)
                return list(requests)
            # cold restart: re-prefill the journaled batch below
            reqs = [r for r in reqs if r.state == RequestState.QUEUED]
        else:
            reqs = [r for r in requests if r.state == RequestState.QUEUED]
        if not reqs:
            return list(requests)
        lens = {int(r.prompt.shape[0]) for r in reqs}
        if len(lens) != 1 or mode == "continuous":
            # mixed prompt lengths (or a continuous-mode cold replay):
            # the continuous scheduler owns the batch
            return self._serve_ragged(requests, reqs, greedy, seed)
        prompts = np.stack([r.prompt for r in reqs]).astype(np.int32)
        self._warm_autotune(prompts.shape[0], prompts.shape[1])
        t_start = time.monotonic()
        if self.journal is not None:
            # batch composition a later cold replay must reproduce
            self.journal.append(
                "serve", fsync=True, rids=[r.rid for r in reqs],
                seed=int(seed), greedy=bool(greedy),
                prompt_len=int(prompts.shape[1]))

        for r in reqs:
            r.state = RequestState.PREFILLING
            if r.admitted_s is None:
                r.admitted_s = t_start
        dev_prompts = jnp.asarray(prompts)
        try:
            logits, cache, path = self._execute(
                "serve.prefill", 0,
                lambda: self._prefill(self.params, dev_prompts),
                lambda: self._prefill_degraded(self.params, dev_prompts))
        except StepFailed as e:
            self._fail_batch(reqs, e)
            return list(requests)
        if path == "degraded":
            self._counters["degraded_steps"] += 1

        for r in reqs:
            r.state = RequestState.DECODING
        self._decode_loop(reqs, cache, logits, 0, t_start, greedy, seed)
        self._check_replay(requests)
        return list(requests)

    def _serve_ragged(self, requests: Sequence[Request],
                      reqs: List[Request], greedy: bool,
                      seed: int) -> List[Request]:
        """Drain a mixed-prompt-length batch through a dedicated
        continuous scheduler.

        A fresh scheduler per call: admission order (the given request
        order), slot assignment and the fixed-shape ragged cache are
        then pure functions of the batch, which is what lets a cold
        journal replay of the same rids regenerate bit-identical
        greedy streams (``_check_replay`` verifies)."""
        self._live = None        # no snapshot point inside a ragged drain
        if self.journal is not None:
            self.journal.append(
                "serve", fsync=True, rids=[r.rid for r in reqs],
                seed=int(seed), greedy=bool(greedy), mode="continuous",
                prompt_lens=[int(r.prompt.shape[0]) for r in reqs])
        sched = ContinuousScheduler(self, self.scheduler_config)
        for r in reqs:
            sched.enqueue(r)
        sched.drain(greedy=greedy, seed=seed)
        self._last_sched_report = sched.report()
        self._check_replay(requests)
        return list(requests)

    # ------------------------------------------------------------------
    # Continuous stepping (the handle/stream API).
    # ------------------------------------------------------------------
    def _ensure_scheduler(self) -> ContinuousScheduler:
        if self._scheduler is None:
            self._scheduler = ContinuousScheduler(self,
                                                  self.scheduler_config)
        return self._scheduler

    def _enqueue_backlog(self, sched: ContinuousScheduler) -> None:
        """Hand submitted-but-unserved handles to the scheduler, in rid
        (submission) order, journaling the in-flight set so a cold
        replay can re-enqueue the identical batch."""
        new = [r for r in self._backlog
               if r.state == RequestState.QUEUED]
        self._backlog = []
        if not new:
            return
        if self.journal is not None:
            live = {r.rid for r in new}
            live.update(r.rid for r in sched.inflight()
                        if not _terminal(r.state))
            self.journal.append(
                "serve", fsync=True, rids=sorted(live),
                seed=int(sched.seed), greedy=bool(sched.greedy),
                mode="continuous")
        for r in new:
            sched.enqueue(r)

    def step(self) -> bool:
        """One continuous-scheduler tick: admit at most one waiting
        request (or push one prefill chunk), then run one decode step
        over every occupied slot.  Returns True if any work was done.
        Newly submitted handles are picked up automatically."""
        self._counters["ticks"] += 1
        with trace.step_span("serve.step", step_num=self._counters["ticks"]):
            sched = self._ensure_scheduler()
            self._enqueue_backlog(sched)
            self._live = None
            return sched.step()

    def drain(self, greedy: bool = True, seed: int = 0) -> None:
        """Step the continuous scheduler until every submitted request
        is terminal."""
        sched = self._ensure_scheduler()
        self._enqueue_backlog(sched)
        self._live = None
        sched.drain(greedy=greedy, seed=seed)

    def scheduler_report(self) -> Optional[Dict[str, Any]]:
        """Occupancy/paging counters: the persistent scheduler's if one
        is live, else the last ragged ``serve()`` drain's (None before
        any continuous serving)."""
        if self._scheduler is not None:
            return self._scheduler.report()
        return getattr(self, "_last_sched_report", None)

    def _decode_loop(self, reqs: List[Request], cache, logits, step: int,
                     t_start: float, greedy: bool, seed: int) -> None:
        """The decode loop, resumable at any ``step``.

        ``reqs`` is the batch in cache-row order (terminal members stay
        inert but keep their rows); ``logits`` predicts the *next*
        token, ``cache`` holds everything up to and including step
        ``step`` — the same pre-step-cache contract the PR-6 retry path
        relies on, which is what makes both snapshot resume and retry
        composable with each other.
        """
        key = jax.random.PRNGKey(seed)
        if not greedy:
            # fast-forward the PRNG stream to the resume position so
            # sampled replay of an unchanged batch is deterministic too
            for _ in range(step):
                key, _ = jax.random.split(key)
        self._live = (reqs, cache, logits, step, greedy, seed)
        while True:
            active = [r for r in reqs if r.state == RequestState.DECODING]
            if not active:
                break
            now = time.monotonic()
            for r in active:
                if (r.deadline_s is not None
                        and now - t_start > r.deadline_s):
                    r.state = RequestState.EVICTED
                    r.error = (f"deadline {r.deadline_s:.3f}s exceeded "
                               f"after {len(r.out_tokens)} tokens")
                    self._counters["evicted"] += 1
                    self.monitor.note("evicted", site="serve.decode_step",
                                      step=step, detail=r.error)
                    self._journal_terminal(r, step)
            active = [r for r in reqs if r.state == RequestState.DECODING]
            if not active:
                break

            if greedy:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, logits).astype(jnp.int32)
            tok_np = np.asarray(tok)
            for i, r in enumerate(reqs):
                if r.state == RequestState.DECODING:
                    t = int(tok_np[i])
                    r.out_tokens.append(t)
                    if self.journal is not None:
                        # position-addressed so a replayed step that
                        # re-emits an already-journaled token overwrites
                        # instead of duplicating on the next recovery
                        self.journal.append("token", rid=r.rid,
                                            step=len(r.out_tokens),
                                            token=t)
                    if len(r.out_tokens) >= r.max_new_tokens:
                        r.state = RequestState.DONE
                        self._counters["completed"] += 1
                        self._journal_terminal(r, step)
            if not any(r.state == RequestState.DECODING for r in reqs):
                break

            step += 1
            try:
                with trace.span("serve.decode", rows=len(active)) as sp:
                    logits, cache, path = self._execute(
                        "serve.decode_step", step,
                        lambda: self._decode(self.params, cache,
                                             tok[:, None]),
                        lambda: self._decode_degraded(self.params, cache,
                                                      tok[:, None]))
            except StepFailed as e:
                self._fail_batch(reqs, e, step)
                break
            if path == "degraded":
                self._counters["degraded_steps"] += 1
                for r in reqs:
                    if r.state == RequestState.DECODING:
                        r.degraded_steps += 1
            self.monitor.record(step, sp.seconds)
            self._live = (reqs, cache, logits, step, greedy, seed)
            if (self.snapshot_every and self.snapshots is not None
                    and step % self.snapshot_every == 0):
                self.snapshot()

    def _journal_terminal(self, r: Request,
                          step: Optional[int] = None) -> None:
        if self.journal is not None:
            self.journal.append(r.state.value, fsync=True, rid=r.rid,
                                step=step, error=r.error)

    def _fail_batch(self, reqs: List[Request], err: BaseException,
                    step: Optional[int] = None) -> None:
        for r in reqs:
            if r.state in (RequestState.PREFILLING, RequestState.DECODING):
                r.state = RequestState.FAILED
                r.error = str(err)
                self._counters["failed"] += 1
                self._journal_terminal(r, step)

    # ------------------------------------------------------------------
    # Crash safety: snapshot, restore, deterministic replay.
    # ------------------------------------------------------------------
    def snapshot(self) -> Optional[int]:
        """Persist the live serve-loop state through the Checkpointer.

        Saved: params, KV cache, last logits (the ``arrays.npz``
        payload) plus the request table, emitted tokens, counters and
        health ledger (the manifest extras).  Returns the snapshotted
        decode step, or None when there is nothing live to snapshot or
        the save failed — a snapshot failure (disk full, injected
        ``snapshot.save``/``ckpt.write`` fault) degrades the recovery
        point, it never takes down serving.
        """
        if self.snapshots is None or self._live is None:
            return None
        reqs, cache, logits, step, greedy, seed = self._live
        try:
            health.maybe_inject("snapshot.save")
            extras = {
                "step": step, "greedy": bool(greedy), "seed": int(seed),
                "rids": [r.rid for r in reqs],
                "requests": [{
                    "rid": r.rid, "state": r.state.value,
                    "prompt": [int(t) for t in r.prompt],
                    "max_new_tokens": r.max_new_tokens,
                    "deadline_s": r.deadline_s,
                    "out_tokens": list(r.out_tokens),
                    "error": r.error,
                } for r in reqs],
                "counters": dict(self._counters),
                "health_events": [[e.kind, e.site, e.step, e.detail]
                                  for e in self.monitor.events],
            }
            self.snapshots.save(
                step,
                {"params": self.params, "cache": cache,
                 "logits": {"arr": logits}},
                extras=extras, blocking=True)
        except (CheckpointError, OSError, health.SimulatedFailure) as e:
            self._counters["snapshot_errors"] += 1
            self.monitor.note("snapshot-error", site="snapshot.save",
                              step=step,
                              detail=f"{type(e).__name__}: {e}")
            return None
        self._counters["snapshots_saved"] += 1
        if self.journal is not None:
            self.journal.append("snapshot", fsync=True, step=step)
        return step

    def restore(self, devices: Optional[Sequence] = None) -> List[Request]:
        """Rebuild journaled requests after a crash; arm the resume.

        Returns every journaled request, in rid order: requests that
        reached a durable terminal state come back exactly as they
        ended (tokens included — nothing lost, nothing duplicated);
        in-flight requests come back re-admitted at their exact decode
        position, ready for the next ``serve()`` call to finish.

        Recovery sources, best to worst: the newest intact snapshot
        (corrupt or fault-injected ones fall back to older steps —
        ``stats()['restore_fallbacks']``), else journal-only cold
        replay (re-prefill + deterministic re-decode).  With
        ``devices`` given, snapshot state is restored through
        ``elastic.plan_remesh`` target shardings, so a restart that
        lost devices recovers onto the surviving mesh.
        """
        if self.journal is None:
            raise ValueError(
                "restore() needs a journal: construct the Engine with "
                "journal_dir= or set REPRO_JOURNAL_DIR")
        records = self.journal.scan()
        table = journal_lib.replay_table(records)
        to_state = {s.value: s for s in RequestState}
        reqs: Dict[int, Request] = {}
        for rid in sorted(table):
            row = table[rid]
            r = Request(prompt=np.asarray(row["prompt"], np.int32),
                        max_new_tokens=row["max_new_tokens"],
                        deadline_s=row["deadline_s"], rid=rid,
                        state=to_state[row["state"]])
            r.out_tokens = list(row["tokens"])
            r.error = row["error"]
            reqs[rid] = r
        if reqs:
            self._next_rid = max(self._next_rid, max(reqs) + 1)

        snap = None
        if self.snapshots is not None:
            for snap_step in reversed(self.snapshots.steps()):
                try:
                    health.maybe_inject("engine.restore")
                    snap = self._load_snapshot(snap_step, devices)
                    break
                except Exception as e:
                    # corrupt snapshot (torn npz/manifest) or injected
                    # fault: quarantine-in-place and fall back — first
                    # to an older snapshot, then to cold replay
                    self._counters["restore_fallbacks"] += 1
                    self.monitor.note(
                        "restore-fallback", site="engine.restore",
                        step=snap_step,
                        detail=f"{type(e).__name__}: {e}")
                    snap = None

        if snap is not None:
            self._arm_snapshot_resume(snap, reqs)
        else:
            self._arm_cold_resume(records, reqs)
        out = [reqs[rid] for rid in sorted(reqs)]
        recovered = [r for r in out if not _terminal(r.state)]
        self._counters["recovered"] += len(recovered)
        self.monitor.note(
            "restore", site="engine.restore",
            detail=f"{len(out)} journaled requests, "
                   f"{len(recovered)} in flight, "
                   f"{'warm' if snap is not None else 'cold'} resume")
        return out

    def _load_snapshot(self, step: int, devices: Optional[Sequence]):
        """Load one snapshot step; raises on any corruption."""
        man = self.snapshots.manifest(step)
        templates = {
            "params": jax.eval_shape(lambda: self.params),
            "cache": {k: 0 for k in man["trees"]["cache"]},
            "logits": {"arr": 0},
        }
        shardings = None
        if devices is not None:
            cache_shape = {
                k: jax.ShapeDtypeStruct(tuple(m["shape"]),
                                        jnp.dtype(m["dtype"]))
                for k, m in man["trees"]["cache"].items()
            }
            plan = elastic.plan_remesh(
                list(devices), templates["params"],
                cache_shape=cache_shape)
            shardings = {"params": plan.param_shardings,
                         "cache": plan.cache_shardings}
        _, state, extras = self.snapshots.restore(
            templates, shardings, step=step)
        return state, extras

    def _arm_snapshot_resume(self, snap, reqs: Dict[int, Request]) -> None:
        """Warm restart: requests re-admitted at the snapshot step."""
        state, extras = snap
        self.params = state["params"]
        step = int(extras["step"])
        snap_reqs = {sr["rid"]: sr for sr in extras.get("requests", [])}
        batch: List[Request] = []
        for rid in extras["rids"]:
            sr = snap_reqs.get(rid, {})
            r = reqs.get(rid)
            if r is None and sr:
                # journal lost the submit record (corruption) — the
                # snapshot's request table is the second source of truth
                r = Request(prompt=np.asarray(sr["prompt"], np.int32),
                            max_new_tokens=sr["max_new_tokens"],
                            deadline_s=sr.get("deadline_s"), rid=rid,
                            state=to_state_safe(sr.get("state")))
                r.out_tokens = list(sr.get("out_tokens", []))
                r.error = sr.get("error")
                reqs[rid] = r
            if r is None:
                raise CheckpointError(
                    f"snapshot step {step} names rid {rid} known to "
                    f"neither journal nor snapshot request table")
            snap_state = to_state_safe(sr.get("state")) if sr else None
            if _terminal(r.state):
                pass                     # journal terminal: authoritative
            elif snap_state is not None and _terminal(snap_state):
                # journal lost the terminal record but the snapshot has
                # it — adopt the snapshot's final word
                r.state = snap_state
                r.out_tokens = list(sr.get("out_tokens", r.out_tokens))
                r.error = sr.get("error", r.error)
            else:
                # journal may be ahead of the snapshot (tokens emitted
                # after the save): keep them as the replay expectation,
                # rewind the live position to the snapshot's
                if len(r.out_tokens) > step:
                    self._replay_expected[rid] = list(r.out_tokens)
                out = sr.get("out_tokens")
                r.out_tokens = (list(out) if out is not None
                                else r.out_tokens[:step])
                self._counters["replayed_steps"] += max(
                    0, len(self._replay_expected.get(rid, []))
                    - len(r.out_tokens))
                r.state = RequestState.DECODING
            batch.append(r)
        for k, v in extras.get("counters", {}).items():
            if k in self._counters:
                self._counters[k] = max(self._counters[k], int(v))
        for kind, site, estep, detail in extras.get("health_events", []):
            self.monitor.events.append(health.HealthEvent(
                kind=kind, site=site, step=estep, detail=detail))
        self._pending_resume = {
            "reqs": batch,
            "cache": state["cache"],
            "logits": state["logits"]["arr"],
            "step": step,
            "greedy": bool(extras["greedy"]),
            "seed": int(extras["seed"]),
        }

    def _arm_cold_resume(self, records: List[dict],
                         reqs: Dict[int, Request]) -> None:
        """No usable snapshot: replay in-flight requests from prefill.

        Greedy decode is a pure function of params + journaled prompt,
        so rewinding to QUEUED and re-serving reproduces the lost
        tokens bit-exactly; the journaled prefix is kept as the replay
        expectation and verified after the resumed serve.
        """
        serves = [rec for rec in records if rec.get("kind") == "serve"]
        if not serves:
            return                      # crash before any serve: QUEUED
        last = serves[-1]
        batch = []
        for rid in last.get("rids", []):
            r = reqs.get(rid)
            if r is None or _terminal(r.state):
                continue
            if r.out_tokens:
                self._replay_expected[rid] = list(r.out_tokens)
                self._counters["replayed_steps"] += len(r.out_tokens)
            r.out_tokens = []
            r.state = RequestState.QUEUED
            batch.append(r)
        if batch:
            self._pending_resume = {
                "reqs": batch, "cache": None, "logits": None, "step": 0,
                "greedy": bool(last.get("greedy", True)),
                "seed": int(last.get("seed", 0)),
                "mode": last.get("mode", "batch"),
            }

    def _take_resume(self, requests: Sequence[Request]):
        """Pop the armed resume iff its batch is inside ``requests``."""
        if self._pending_resume is None:
            return None
        given = {id(r) for r in requests}
        if all(id(r) in given for r in self._pending_resume["reqs"]):
            pending, self._pending_resume = self._pending_resume, None
            return pending
        return None

    def _check_replay(self, requests: Sequence[Request]) -> None:
        """Verify re-decoded tokens against the pre-crash journal.

        Determinism makes the replayed prefix bit-identical; a
        divergence means corrupted state (bad snapshot, bit-flipped
        journal record, changed params) and is ledgered loudly — the
        recomputed tokens win, since they came from the live model.
        """
        for r in requests:
            exp = self._replay_expected.pop(r.rid, None)
            if exp is None:
                continue
            n = min(len(exp), len(r.out_tokens))
            if r.out_tokens[:n] != exp[:n]:
                self._counters["replay_divergence"] += 1
                self.monitor.note(
                    "replay-divergence", site="engine.restore",
                    detail=f"rid {r.rid}: journaled {exp[:n]} vs "
                           f"replayed {r.out_tokens[:n]}")

    def stats(self) -> Dict[str, object]:
        """Admission/backpressure counters merged with the health
        ledger rollup (``HealthMonitor.report``) and, when configured,
        the journal/snapshot durability counters."""
        out: Dict[str, object] = dict(self._counters)
        out["demoted_now"] = self.policy.demoted
        out["probes"] = self.policy.probes
        out["health"] = self.monitor.report()
        sched = self.scheduler_report()
        if sched is not None:
            out["scheduler"] = sched
        if self.journal is not None:
            out["journal"] = self.journal.stats()
        if self.snapshots is not None:
            out["snapshots"] = self.snapshots.stats()
        out["trace"] = trace.report()
        return out

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 greedy: bool = True, seed: int = 0) -> np.ndarray:
        """prompts: (B, S) equal-length int32. Returns (B, new) tokens.

        .. deprecated:: PR 8
           ``generate`` is a back-compat shim over ``submit`` +
           ``drain``; use ``submit()`` and stream the returned
           ``RequestHandle`` (``handle.tokens()`` / ``handle.result()``)
           or batch with ``serve()``/``drain()`` directly.

        Raises ``StepFailed`` on any request that does not finish DONE.
        """
        warnings.warn(
            "Engine.generate() is deprecated; use Engine.submit() and "
            "stream the RequestHandle (tokens()/result()), or "
            "serve()/drain() for batches",
            DeprecationWarning, stacklevel=2)
        prompts = np.asarray(prompts)
        reqs = [self.submit(p, max_new_tokens) for p in prompts]
        self.drain(greedy=greedy, seed=seed)
        bad = [r for r in reqs if r.state != RequestState.DONE]
        if bad:
            r = bad[0]
            raise StepFailed(
                f"request {r.rid} ended {r.state.value}: {r.error}")
        return np.stack(
            [np.asarray(r.out_tokens, np.int32) for r in reqs])
