"""Persistent autotuned dataflow-spec cache (PolyDL-style memoization).

``best_spec`` memoizes ``explorer.best_spec`` so the candidate space is
enumerated and ranked at most once per distinct workload, per process —
and, via a small on-disk JSON store, at most once per machine.

Key schema (``_key``): a flat string over every field that changes the
ranking, built generically from the problem registry
(``core.dataflow.register_problem``) —

    v<CACHE_VERSION>|<kind>|<key_fields...>
                    |hw=<name>|vmem=<bytes>|backend=<pallas/interpret/xla>

where ``kind`` tags the subsystem and ``key_fields`` come from its
registration:

    gemm — m|k|n|in_dtype|out_dtype|acc_dtype
    conv — full conv geometry n|ih|iw|fh|fw|s|cin|cout|dtypes (two convs
           with the same implicit-GEMM view but different filter/stride
           have different window reuse and VMEM needs); specs are
           conv-blocked ``(b_oh, bc, bk)`` (see
           ``cost_model.conv_gemm_view``)
    bin  — packed geometry m|kp|n plus the true reduction depth n_bits
           (two packings of different-K layers can share a ``kp`` but
           differ in bit-ops); ``block`` = ``(bm, bkp, bn)`` in words
    attn — bh|sq|skv|d|group|causal|window|dtype|kv_len|kv_dtype;
           ``block`` = ``(bq, bkv, d)`` over the OS(flash)/
           WS(kv-stationary) anchors; ``kv_len`` (the valid KV prefix
           of a padded cache buffer — traced lengths key as the
           ``kl-`` worst case) and ``kv_dtype`` (int8 KV cache) both
           move the banded traffic ranking

Disk location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune.json``.  Invalidation: entries embed the key
schema version, so bumping ``CACHE_VERSION`` (e.g. when the cost model
or kernel lowering changes materially) orphans every stale entry;
deleting the file forces a full re-tune.  Disk I/O is best-effort — a
read-only filesystem degrades to the in-process cache.

Corruption recovery: a cache file that fails to parse is *quarantined*
(renamed to ``autotune.json.corrupt-<n>``) so the evidence survives for
a post-mortem instead of being silently ignored or — worse — crashing
serving.  Within a parseable file every entry is validated
independently: each carries a CRC32 checksum of its spec payload, and
a malformed or checksum-mismatched entry is skipped (counted in
``stats()['entries_skipped']``) while the good entries load normally.
Saves are atomic (temp file + ``os.replace``) so a mid-write kill can
never leave a torn store — the ``autotune.save`` fault-injection site
drills exactly that (see runtime/health.py).

``CACHE_VERSION`` history: 1 = GEMM-only keys (PR 1); 2 = conv keys
added alongside the single-dispatch conv lowering (PR 2) — the conv
kernel change shifts realized traffic, so v1 entries are orphaned;
3 = binary keys added alongside the explored binary anchors (PR 3) —
the binary kernel's blocking became spec-driven, so v2 entries are
orphaned; 4 = registry-generic keys (every kind is tagged, GEMM keys
gained the ``gemm`` segment) + attention keys (PR 4); 5 = attention
keys gained the ``kv_len``/``kv_dtype`` segments alongside the banded
(block-skipping) cost model and kernel lowerings (PR 5) — v4 attention
rankings were computed under full-mask accounting, so every v4 entry
is orphaned; 6 = GEMM/conv keys gained the ``wb<bits>`` packing segment
alongside the sub-byte packed-weight datapath (PR 9) — the cost model
now charges packed-plane + outlier-sidecar bytes for weight traffic,
so v5 GEMM/conv rankings are stale and every v5 entry is orphaned.

An optional *empirical refinement* pass (``refine=True``) re-ranks the
analytical top-k by wall clock on the backend being tuned (the Pallas
interpreter only for ``backend="interpret"``) before caching, trading
one-off tuning time for a measured winner — the PolyDL observation that
autotuned selection over a pruned space beats a purely analytical pick.
The re-rank runs through the registration's ``measure`` hook, so every
registered subsystem (GEMM, conv, binary, attention) refines the same
way.  With ``refine=None`` (the default) the pass is enabled by setting
``REPRO_AUTOTUNE_REFINE=1`` in the environment; it changes only which
feasible spec is picked, never the numerics of the op that consumes it.
"""
from __future__ import annotations

import json
import os
import tempfile
import zlib
from typing import Any, Dict, Iterable, List, Optional

from repro.core import cost_model, explorer
from repro.core.dataflow import (
    DataflowSpec,
    Residency,
    Stationarity,
    registration_for,
)

CACHE_VERSION = 6

# Any problem type carrying a ``core.dataflow`` registration resolves
# here — deliberately not a closed Union, so onboarding a subsystem
# never edits this module.
Problem = Any

_memory: Dict[str, DataflowSpec] = {}
_disk_loaded = False
_defer_save = False  # warm() batches misses into one disk write
_stats = {
    "lookups": 0,       # best_spec calls
    "hits": 0,          # served from memory or disk
    "misses": 0,        # required an enumeration
    "enumerations": 0,  # explorer.explore invocations (incl. refinement)
    "entries_loaded": 0,        # disk entries accepted by validation
    "entries_skipped": 0,       # malformed / checksum-failed entries
    "files_quarantined": 0,     # unparseable stores moved aside
    "load_errors": 0,           # I/O or injected faults during load
    "save_errors": 0,           # I/O or injected faults during save
}


def _key(problem: Problem, hw: cost_model.HardwareSpec,
         backend: str) -> str:
    reg = registration_for(problem)
    return "|".join([
        f"v{CACHE_VERSION}", reg.kind, *reg.key_fields(problem),
        f"hw={hw.name}", f"vmem={hw.vmem_bytes}", f"backend={backend}",
    ])


def cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "autotune.json"
    )


def _spec_to_json(spec: DataflowSpec) -> dict:
    return {
        "anchor": spec.anchor.value,
        "aux": [[st.value, res.value] for st, res in spec.aux],
        "aux_priority": [st.value for st in spec.aux_priority],
        "block": list(spec.block),
        "vmem_budget": spec.vmem_budget,
    }


def _spec_from_json(d: dict) -> DataflowSpec:
    return DataflowSpec(
        anchor=Stationarity(d["anchor"]),
        aux={Stationarity(s): Residency(r) for s, r in d["aux"]},
        aux_priority=tuple(Stationarity(s) for s in d["aux_priority"]),
        block=tuple(d["block"]),
        vmem_budget=d["vmem_budget"],
    )


def _checksum(spec_json: dict) -> int:
    """CRC32 of the canonical JSON encoding of a spec payload."""
    blob = json.dumps(spec_json, sort_keys=True,
                      separators=(",", ":")).encode()
    return zlib.crc32(blob) & 0xFFFFFFFF


def _entry_to_json(spec: DataflowSpec) -> dict:
    payload = _spec_to_json(spec)
    return {"spec": payload, "sum": _checksum(payload)}


def _entry_from_json(entry: dict) -> Optional[DataflowSpec]:
    """Validate ONE disk entry; None means skip (never raise).

    Accepts only the checksummed ``{"spec": ..., "sum": ...}`` envelope
    whose CRC matches; anything else — a truncated object, a bit-flipped
    payload, a pre-checksum legacy entry — is rejected individually so
    one bad record cannot poison its neighbors.
    """
    if not isinstance(entry, dict):
        return None
    payload = entry.get("spec")
    if not isinstance(payload, dict) or "sum" not in entry:
        return None
    try:
        if int(entry["sum"]) != _checksum(payload):
            return None
        return _spec_from_json(payload)
    except (KeyError, ValueError, TypeError):
        return None


def _quarantine(path: str) -> Optional[str]:
    """Move an unreadable cache file to ``<path>.corrupt-<n>``.

    Keeps the evidence for debugging and guarantees the next save starts
    from a clean slate; returns the quarantine path (None if the rename
    itself failed, e.g. on a read-only filesystem)."""
    for n in range(100):
        target = f"{path}.corrupt-{n}"
        if not os.path.exists(target):
            break
    else:
        target = f"{path}.corrupt-overflow"
    try:
        os.replace(path, target)
    except OSError:
        return None
    _stats["files_quarantined"] += 1
    return target


def _load_disk() -> None:
    """Best-effort disk load with per-entry validation.

    Failure containment, from coarse to fine: an I/O error or injected
    ``autotune.load`` fault degrades to the in-process cache (counted,
    never raised past here); an unparseable file is quarantined to
    ``autotune.json.corrupt-<n>``; a parseable file with some malformed
    or checksum-failed entries keeps every good entry and counts the
    skips in ``stats()``.  A version mismatch is not corruption — the
    orphaned store is left in place and simply ignored.
    """
    from repro.runtime import health

    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    path = cache_path()
    try:
        health.maybe_inject("autotune.load")
        with open(path) as f:
            raw = f.read()
    except FileNotFoundError:
        return
    except (OSError, health.SimulatedFailure):
        _stats["load_errors"] += 1
        return
    try:
        data = json.loads(raw)
        if not isinstance(data, dict):
            raise ValueError("cache root is not an object")
    except ValueError:
        _quarantine(path)
        return
    if data.get("version") != CACHE_VERSION:
        return
    entries = data.get("entries")
    if not isinstance(entries, dict):
        _quarantine(path)
        return
    for key, entry in entries.items():
        if key in _memory:
            continue
        spec = _entry_from_json(entry)
        if spec is None:
            _stats["entries_skipped"] += 1
            continue
        _memory[key] = spec
        _stats["entries_loaded"] += 1


def _save_disk() -> None:
    """Atomic, best-effort rewrite of the whole store.

    The payload is fully serialized into a temp file in the target
    directory and moved into place with ``os.replace``, so a reader can
    never observe a torn store and a mid-write kill (drilled via the
    ``autotune.save`` fault site) leaves the previous file intact.
    """
    from repro.runtime import health

    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        payload = {
            "version": CACHE_VERSION,
            "entries": {k: _entry_to_json(s) for k, s in _memory.items()},
        }
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path), suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
                # the injected mid-write kill lands here: after bytes hit
                # the temp file but before the atomic rename
                health.maybe_inject("autotune.save")
            os.replace(tmp, path)
        except BaseException:
            os.unlink(tmp)
            raise
    except (OSError, health.SimulatedFailure):
        _stats["save_errors"] += 1


def refine_enabled() -> bool:
    """The ``REPRO_AUTOTUNE_REFINE=1`` env flag (ROADMAP PR-1 open item):
    opt-in empirical re-ranking of the analytical top-k on cache misses."""
    return os.environ.get("REPRO_AUTOTUNE_REFINE", "") == "1"


def best_spec(
    problem: Problem,
    hw: Optional[cost_model.HardwareSpec] = None,
    backend: str = "pallas",
    refine: Optional[bool] = None,
    refine_top: int = 3,
) -> DataflowSpec:
    """Cached explorer pick for ``problem`` on ``hw``/``backend``.

    Fully registry-driven: any problem type registered via
    ``core.dataflow.register_problem`` resolves here — the cache key,
    the candidate enumeration (through the generic ``explorer.explore``)
    and the optional empirical refinement all come from the problem's
    registration.  Block semantics are per-subsystem (GEMM
    ``(bm, bk, bn)``, conv ``(b_oh, bc, bk)``, binary ``(bm, bkp, bn)``
    in packed words, attention ``(bq, bkv, d)``).  ``refine=None``
    defers to the ``REPRO_AUTOTUNE_REFINE=1`` env flag (default off);
    the re-rank runs the registration's ``measure`` hook on the
    analytical top-k, timed on the backend being tuned (interpret mode
    only when ``backend == "interpret"``).  ``hw=None`` takes the
    device's model (``cost_model.hardware_for``).
    """
    if hw is None:
        hw = cost_model.hardware_for()
    if refine is None:
        refine = refine_enabled()
    _load_disk()
    reg = registration_for(problem)
    key = _key(problem, hw, backend)
    _stats["lookups"] += 1
    spec = _memory.get(key)
    if spec is not None:
        _stats["hits"] += 1
        return spec
    _stats["misses"] += 1
    _stats["enumerations"] += 1
    ranked = explorer.explore(problem, hw, top=max(1, refine_top))
    if not ranked:
        raise ValueError(f"no feasible dataflow for {problem}")
    spec = ranked[0].spec
    if refine and reg.measure is not None and len(ranked) > 1:
        measured = reg.measure(problem, [c.spec for c in ranked],
                               interpret=backend == "interpret")
        spec = measured[0][0]
    _memory[key] = spec
    if not _defer_save:
        _save_disk()
    return spec


def warm(
    problems: Iterable[Problem],
    hw: Optional[cost_model.HardwareSpec] = None,
    backend: str = "pallas",
) -> List[DataflowSpec]:
    """Pre-populate the cache for a known set of hot workloads (any
    registered problem types — GEMM, conv, binary, attention — mix
    freely).

    Misses are batched into a single disk write at the end instead of
    one full-store rewrite per problem.  Problems with no feasible
    dataflow (e.g. a conv whose image exceeds VMEM) are skipped rather
    than aborting the warm-up — the op will raise at call time instead.
    """
    global _defer_save
    if hw is None:
        hw = cost_model.hardware_for()
    before = _stats["misses"]
    _defer_save = True
    specs = []
    try:
        for p in problems:
            try:
                specs.append(best_spec(p, hw, backend))
            except ValueError:
                continue
    finally:
        _defer_save = False
    if _stats["misses"] > before:
        _save_disk()
    return specs


def stats() -> Dict[str, int]:
    return dict(_stats)


def reset_stats() -> None:
    for k in _stats:
        _stats[k] = 0


def clear(disk: bool = False) -> None:
    """Drop the in-process cache; with ``disk=True`` also the JSON store."""
    global _disk_loaded
    _memory.clear()
    _disk_loaded = False
    if disk:
        try:
            os.unlink(cache_path())
        except OSError:
            pass
