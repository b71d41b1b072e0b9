"""Health monitoring: heartbeats, stragglers, fault injection, degradation.

On a real multi-host deployment each host runs a ``HealthMonitor``; the
coordinator aggregates heartbeats and triggers checkpoint-restart (via
runtime/driver.py) or elastic remesh (runtime/elastic.py) on dead hosts.
The serving engine (serve/engine.py) runs the same monitor per decode
loop, so stragglers, retries and kernel demotions surface in one ledger.

Fault injection is unified behind *named sites*: every place the stack
can plausibly fail — the serve loop, the autotune cache, each kernel
dispatch point, the train step — calls ``maybe_inject(site)``.  The
``REPRO_FAULT_PLAN`` env var arms faults declaratively::

    REPRO_FAULT_PLAN="<site>:<step>:<kind>[,<site>:<step>:<kind>...]"

where ``step`` is the 0-based hit count of that site at which the fault
fires (``*`` = every hit) and ``kind`` is one of

    raise         raise SimulatedFailure at the site
    nan           ask the caller to poison its output with NaNs
                  (``maybe_inject`` returns ``"nan"``; numeric sites
                  multiply their result by NaN, exercising the
                  non-finite sentinel downstream)
    hang-timeout  sleep ``REPRO_FAULT_HANG_S`` seconds (default 0.25)
                  before continuing — a straggler, not a crash
    kill          SIGKILL the whole process at the site — an
                  *unhandleable* crash (no finally blocks, no atexit,
                  no flushing).  The crash-drill CI job arms this at
                  journaled serve steps and asserts the restarted
                  engine replays bit-exactly (serve/journal.py)

Sites inside jit-traced code (the ``kernel.*`` and ``layers.*`` family)
fire at trace/lowering time — once per distinct compiled shape — which
is exactly where real lowering failures surface; host-side sites
(``serve.*``, ``autotune.*``, ``train.step``) fire on every call.
``REPRO_FAIL_AT_STEP`` is kept as sugar for ``train.step:<n>:raise``
keyed on the *training* step number (which survives checkpoint-restart,
unlike the per-process hit counter).
"""
from __future__ import annotations

import collections
import dataclasses
import os
import signal
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple


class SimulatedFailure(RuntimeError):
    """Raised by an armed ``raise``-kind injection site."""


FAULT_KINDS = ("raise", "nan", "hang-timeout", "kill")

# Canonical injection sites.  Modules owning additional dispatch points
# register theirs at import time via ``register_site`` — the CI fault
# drill iterates this set, so a site that is never registered is a site
# that is never drilled.
INJECTION_SITES: List[str] = [
    "serve.prefill",
    "serve.decode_step",
    "autotune.load",
    "autotune.save",
    "kernel.matmul",
    "kernel.conv2d",
    "kernel.binary_matmul",
    "kernel.attention",
    "layers.attention",
    "layers.mlp",
    "train.step",
    "pool.alloc",
    "pool.spill",
]


def register_site(site: str) -> str:
    """Idempotently add ``site`` to the drillable-site registry."""
    if site not in INJECTION_SITES:
        INJECTION_SITES.append(site)
    return site


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    site: str
    step: Optional[int]      # None = every hit ("*")
    kind: str                # raise | nan | hang-timeout


@dataclasses.dataclass
class FiredFault:
    site: str
    hit: int
    kind: str
    timestamp: float


def parse_fault_plan(plan: str) -> List[FaultSpec]:
    """Parse a ``site:step:kind[,...]`` spec; raises ValueError on a
    malformed entry so a typo'd drill fails loudly, not silently."""
    specs: List[FaultSpec] = []
    for part in plan.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.rsplit(":", 2)
        if len(fields) != 3:
            raise ValueError(f"fault plan entry {part!r} is not "
                             f"site:step:kind")
        site, step_s, kind = fields
        if kind == "hang":
            kind = "hang-timeout"
        if kind not in FAULT_KINDS:
            raise ValueError(
                f"fault kind {kind!r} not in {FAULT_KINDS}")
        step = None if step_s == "*" else int(step_s)
        specs.append(FaultSpec(site=site, step=step, kind=kind))
    return specs


_site_hits: Dict[str, int] = {}
_fired: List[FiredFault] = []


def reset_faults() -> None:
    """Zero the per-site hit counters and the fired-fault log."""
    _site_hits.clear()
    _fired.clear()


def fault_log() -> List[FiredFault]:
    """Every fault the plan has fired so far, in firing order."""
    return list(_fired)


def fault_hang_seconds() -> float:
    return float(os.environ.get("REPRO_FAULT_HANG_S", "0.25"))


def _active_plan() -> List[FaultSpec]:
    plan = os.environ.get("REPRO_FAULT_PLAN")
    return parse_fault_plan(plan) if plan else []


def maybe_inject(site: str, step: Optional[int] = None) -> Optional[str]:
    """Advance ``site``'s hit counter and fire any armed fault.

    ``step`` overrides the hit index used for matching (the train driver
    passes the real training step so ``REPRO_FAIL_AT_STEP`` semantics
    survive restarts); by default the per-process hit count is used.

    Returns the fired kind for faults the *caller* must realize
    (``"nan"``: poison your output; ``"hang-timeout"``: the sleep
    already happened), ``None`` when nothing fired.  ``raise``-kind
    faults raise ``SimulatedFailure``.
    """
    hit = _site_hits.get(site, 0)
    _site_hits[site] = hit + 1
    idx = hit if step is None else step
    if site == "train.step":
        at = os.environ.get("REPRO_FAIL_AT_STEP")
        if at is not None and idx == int(at):
            _fired.append(FiredFault(site, idx, "raise", time.time()))
            raise SimulatedFailure(f"injected failure at step {idx}")
    for spec in _active_plan():
        if spec.site != site:
            continue
        if spec.step is not None and spec.step != idx:
            continue
        _fired.append(FiredFault(site, idx, spec.kind, time.time()))
        if spec.kind == "raise":
            raise SimulatedFailure(
                f"injected failure at {site} (hit {idx})")
        if spec.kind == "kill":
            # A real crash: SIGKILL cannot be caught, so nothing below
            # this frame (journal fsyncs, checkpoint renames, atexit)
            # gets to run — exactly the window crash recovery must
            # survive.
            os.kill(os.getpid(), signal.SIGKILL)
        if spec.kind == "hang-timeout":
            time.sleep(fault_hang_seconds())
        return spec.kind
    return None


def maybe_inject_failure(step: int) -> None:
    """Legacy hook (REPRO_FAIL_AT_STEP): crash the training loop at a
    chosen step.  Now a thin wrapper over the ``train.step`` site, so a
    ``REPRO_FAULT_PLAN`` targeting ``train.step`` fires here too."""
    maybe_inject("train.step", step=step)


# ---------------------------------------------------------------------------
# Health ledger.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StepRecord:
    step: int
    seconds: float
    timestamp: float


@dataclasses.dataclass
class HealthEvent:
    """One ledger row: what happened, where, at which step."""

    kind: str                    # demotion | retry | probe | straggler |
    #                              admission-reject | fault | evicted | ...
    site: str = ""
    step: Optional[int] = None
    detail: str = ""
    timestamp: float = dataclasses.field(default_factory=time.time)


class HealthMonitor:
    """Per-host step timing + straggler detection + event ledger.

    A step is flagged a straggler when it exceeds ``threshold`` x the
    rolling median of the last ``window`` steps.  The monitor keeps only
    those steps and the last ``window`` stragglers, with exact counts of
    both, so its memory does not grow with the steps it has seen.  At
    cluster scale the same statistic over per-host heartbeats identifies
    slow hosts; the mitigation hook is pluggable (default: record + warn
    — a production deployment plugs in hot-spare promotion or in-flight
    re-dispatch).

    Beyond timing, the monitor is the single *ledger* for the serving
    stack: kernel demotions, retries, Pallas re-probes, admission
    rejections and injected faults all land in ``events`` via ``note``,
    and ``report()`` rolls them up next to the straggler stats.
    """

    def __init__(self, window: int = 32, threshold: float = 3.0,
                 on_straggler: Optional[Callable[[StepRecord], None]] = None):
        self.window = window
        self.threshold = threshold
        self.records: Deque[StepRecord] = collections.deque(maxlen=window)
        self.stragglers: List[StepRecord] = []     # the last ``window``
        self.steps = 0
        self.straggler_count = 0
        self.on_straggler = on_straggler
        self.events: List[HealthEvent] = []

    def record(self, step: int, seconds: float) -> bool:
        rec = StepRecord(step, seconds, time.time())
        recent = [r.seconds for r in self.records]
        self.records.append(rec)
        self.steps += 1
        if len(recent) >= 8:
            med = sorted(recent)[len(recent) // 2]
            if seconds > self.threshold * med:
                self.straggler_count += 1
                self.stragglers.append(rec)
                del self.stragglers[:-self.window]
                self.note("straggler", step=step,
                          detail=f"{seconds:.3f}s vs median {med:.3f}s")
                if self.on_straggler:
                    self.on_straggler(rec)
                return True
        return False

    def note(self, kind: str, site: str = "", step: Optional[int] = None,
             detail: str = "") -> HealthEvent:
        ev = HealthEvent(kind=kind, site=site, step=step, detail=detail)
        self.events.append(ev)
        return ev

    def events_of(self, kind: str) -> List[HealthEvent]:
        return [e for e in self.events if e.kind == kind]

    @property
    def median_step_seconds(self) -> float:
        """The median of the last ``window`` steps."""
        if not self.records:
            return 0.0
        xs = sorted(r.seconds for r in self.records)
        return xs[len(xs) // 2]

    def report(self) -> Dict[str, object]:
        """One-stop health rollup: step timing, stragglers, and the
        event ledger grouped by kind."""
        by_kind: Dict[str, int] = {}
        for e in self.events:
            by_kind[e.kind] = by_kind.get(e.kind, 0) + 1
        return {
            "steps": self.steps,
            "median_step_seconds": self.median_step_seconds,
            "stragglers": self.straggler_count,
            "events": by_kind,
            "injected_faults": [
                (f.site, f.hit, f.kind) for f in fault_log()
            ],
        }


# ---------------------------------------------------------------------------
# Graceful kernel degradation.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DegradationPolicy:
    """When and how the serving engine falls back to the XLA path.

    The engine asks ``backend_for(step)`` before every prefill/decode
    step: ``"primary"`` means the configured (Pallas-on-TPU) path,
    ``"degraded"`` means the ``backend="xla"`` escape hatch
    (``layers.forced_backend``).  ``on_failure`` demotes after a step
    failure (kernel lowering error, injected fault, non-finite logits);
    after ``cooldown_steps`` degraded steps the next step *re-probes*
    the primary path — a healthy probe promotes back, a failing one
    re-demotes for another cooldown.  ``max_retries``/``backoff_base_s``
    bound the per-step retry loop (exponential backoff) for transient
    failures that survive demotion.
    """

    max_retries: int = 2
    backoff_base_s: float = 0.02
    cooldown_steps: int = 4

    def __post_init__(self):
        self.demoted = False
        self.demoted_at: Optional[int] = None
        self.demotions: List[Tuple[str, int]] = []   # (site, step)
        self.probes = 0

    def backend_for(self, step: int,
                    monitor: Optional[HealthMonitor] = None) -> str:
        if not self.demoted:
            return "primary"
        if step - self.demoted_at >= self.cooldown_steps:
            self.probes += 1
            if monitor is not None:
                monitor.note("probe", step=step,
                             detail="re-probing primary kernel path "
                                    "after cooldown")
            self.demoted = False          # optimistic: re-demote on failure
            self.demoted_at = None
            return "primary"
        return "degraded"

    def on_failure(self, site: str, step: int, error: BaseException,
                   monitor: Optional[HealthMonitor] = None) -> None:
        self.demoted = True
        self.demoted_at = step
        self.demotions.append((site, step))
        if monitor is not None:
            monitor.note("demotion", site=site, step=step,
                         detail=f"{type(error).__name__}: {error}")

    def backoff_seconds(self, attempt: int) -> float:
        return self.backoff_base_s * (2 ** attempt)
