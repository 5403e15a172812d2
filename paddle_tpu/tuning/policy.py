"""Decision resolution: swept-DB hit -> analytic prior -> default.

`decide()` is the one consult point every tunable lever flows through
(conv lowering, attention backend, conv+BN fusion, AMP list membership,
bucket boundaries, the expert and state-update kernels). Three tiers,
strictly ordered:

  1. exact hit  — the swept DB has this (op, shape, dtype, device_kind) key;
  2. analytic   — the registered prior for the op kind (the conv cost model,
                  the shape rules of the attention and expert kernels);
  3. default    — the caller's conservative fallback.

Every resolution bumps a per-op provenance counter (`provenance_snapshot`):
how much of a workload ran on swept decisions vs the prior.

Modes (FLAGS_tuning_mode):
  off     — decide() is never consulted; levers use their own shape rules.
  consult — resolve through the tiers above.
  sweep   — resolve analytically like `off`, but RECORD every distinct key
            encountered into the DB as a `candidate` entry (never clobbering
            a swept verdict) so `tools/tune.py` knows what to measure.
"""
from __future__ import annotations

import threading

from .. import flags
from .db import TuningDB

__all__ = ["decide", "mode", "sweep_enabled", "get_db",
           "invalidate_db_cache", "device_kind", "provenance_snapshot",
           "reset_provenance", "on_minimize"]

_lock = threading.Lock()
_db_cache: tuple[str, float, TuningDB] | None = None  # (path, mtime, db)

# provenance counters: {op: {"db": n, "analytic": n, "default": n}}
_counters: dict[str, dict[str, int]] = {}


def mode() -> str:
    m = str(flags.get_flag("tuning_mode")).strip().lower()
    return m if m in ("off", "consult", "sweep") else "off"


def sweep_enabled() -> bool:
    return mode() == "sweep"


_device_kind: str | None = None


def device_kind() -> str:
    """Canonical device component of every key. Cached after the first
    backend query — decide() runs inside jit traces."""
    global _device_kind
    if _device_kind is not None:
        return _device_kind
    import jax

    # a backend that fails to initialise raises here: keying every decision
    # under "cpu" instead would consult the wrong device's verdicts
    _device_kind = str(jax.devices()[0].device_kind)
    return _device_kind


def get_db() -> TuningDB:
    """The DB for FLAGS_tuning_db, reloaded when the file's mtime moves
    (a sweep finishing mid-session is picked up without a restart). An
    empty/unset path is a permanently-empty DB (pure analytic mode)."""
    global _db_cache
    path = str(flags.get_flag("tuning_db")).strip()
    if not path:
        return TuningDB(None)
    import os

    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        mtime = -1.0
    with _lock:
        if _db_cache and _db_cache[0] == path and _db_cache[1] == mtime:
            return _db_cache[2]
        db = TuningDB(path)
        _db_cache = (path, mtime, db)
        return db


def invalidate_db_cache() -> None:
    global _db_cache
    with _lock:
        _db_cache = None


def _bump(op: str, tier: str) -> None:
    with _lock:
        c = _counters.setdefault(op, {"db": 0, "analytic": 0, "default": 0})
        c[tier] = c.get(tier, 0) + 1
    from .. import observability as obs

    obs.counter_inc("tuning.decisions", labels={"op": op, "tier": tier})


def reset_provenance() -> None:
    with _lock:
        _counters.clear()


def provenance_snapshot() -> dict:
    """Per-op tier counts plus the aggregate rate: hit_rate is swept-DB
    resolutions over all resolutions."""
    with _lock:
        per_op = {op: dict(c) for op, c in _counters.items()}
    total = sum(sum(c.values()) for c in per_op.values())
    hits = sum(c["db"] for c in per_op.values())
    return {
        "decisions": total,
        "db_hits": hits,
        "hit_rate": round(hits / total, 4) if total else None,
        "per_op": per_op,
    }


def decide(op: str, key: str, prior=None, default: dict | None = None,
           validate=None) -> tuple[dict, str]:
    """Resolve one decision. Returns (decision dict, tier) with tier in
    {"db", "analytic", "default"}.

    `prior`: zero-arg callable returning the analytic decision (evaluated
    lazily — cost models only run on a DB miss). `validate`: optional
    predicate on a DB decision; a decision the current build cannot honor
    (e.g. a pallas backend off-TPU) falls through to the prior instead of
    being obeyed blindly. In sweep mode the analytic resolution is recorded
    as a candidate entry for tools/tune.py."""
    if sweep_enabled():
        d = _resolve_prior(op, prior, default)
        _record_candidate(key, d)
        return d
    entry = get_db().lookup(key)
    if entry is not None and entry.get("source") != "candidate":
        decision = entry["decision"]
        if validate is None or validate(decision):
            _bump(op, "db")
            return decision, "db"
    return _resolve_prior(op, prior, default)


def _resolve_prior(op, prior, default):
    if prior is not None:
        d = prior()
        if d is not None:
            _bump(op, "analytic")
            return d, "analytic"
    _bump(op, "default")
    return dict(default or {}), "default"


_seen_candidates: set[str] = set()


def _record_candidate(key: str, resolved: tuple[dict, str]) -> None:
    """Sweep mode: persist the key (with its analytic resolution as the
    provisional decision) so the offline sweeper knows the workload's
    decision surface. Write-through is cheap — each distinct key is recorded
    once per process and the file is small."""
    if key in _seen_candidates:
        return
    _seen_candidates.add(key)
    path = str(flags.get_flag("tuning_db")).strip()
    if not path:
        return
    db = get_db()
    if db.put(key, resolved[0], source="candidate",
              note=f"analytic resolution tier={resolved[1]}",
              overwrite=False):
        try:
            db.save(path)
            invalidate_db_cache()  # mtime moved; reload clean next consult
        except OSError:
            pass  # read-only FS: candidates stay in-memory only


def on_minimize(program) -> None:
    """minimize()-time hook (optimizer.Optimizer.backward): force the DB
    load NOW so a corrupt file warns at graph-build time — once, attached to
    the minimize call — rather than somewhere inside an op trace, and stamp
    the program with the mode it was built under (bench provenance)."""
    m = mode()
    program._tuning_mode = m
    if m != "off":
        get_db()
