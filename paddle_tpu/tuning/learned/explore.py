"""Bounded online exploration: sweep-in-production (FLAGS_tuning_mode=explore).

Consult mode leaves `candidate` DB entries forever unmeasured unless an
offline `tools/tune.py --what candidates` run happens to visit the box.
Explore mode closes that loop from inside the running job, with the TVM
bounds (arXiv:1802.04799) that make online measurement safe:

  * paced      — at most ONE candidate is probed every
                 FLAGS_tuning_explore_every executor steps (the probe rides
                 the window-drain idle gap at the end of run_async; steady
                 training throughput, not the probe, owns the device);
  * bounded    — each probe is a handful of tiny timed windows
                 (EXPLORE_ITERS x EXPLORE_PASSES), never an open-ended
                 sweep;
  * band-gated — a verdict is accepted ONLY outside the interference band
                 (max of the 5% floor and every arm's measured spread); a
                 tie keeps the candidate AND attaches the evidence, so a
                 later offline sweep starts from data, not zero;
  * write-equal — promotions land as `source="swept"` entries with the
                 SAME measured-evidence schema offline sweeps write
                 (db.evidence), so nothing downstream can tell who swept.

Every probe's raw windows also land in the measurement store
(source="explore") — exploration grows the learned tier's training set as
a side effect, which is the whole point.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from ... import flags
from ..db import evidence
from . import features, store

# tools/_timing.DEFAULT_BAND (tools/ is not importable from the package):
# margins inside 5% are machine noise, not a measured win
EXPLORE_BAND = 0.05
EXPLORE_ITERS = 2
EXPLORE_PASSES = 3

__all__ = ["maybe_explore", "explore_one", "reset_state",
           "EXPLORE_BAND", "EXPLORE_ITERS", "EXPLORE_PASSES"]

_lock = threading.Lock()
_state = {"steps": 0, "done": set()}


def reset_state() -> None:
    with _lock:
        _state["steps"] = 0
        _state["done"] = set()


def maybe_explore() -> dict | None:
    """The executor's per-step hook: cheap no-op outside explore mode; in
    it, every Nth step probes the next unmeasured candidate. Returns the
    probe result dict (or None) — callers ignore it; tests don't."""
    from .. import policy

    if policy.mode() != "explore":
        return None
    try:
        every = int(flags.get_flag("tuning_explore_every"))
    except (TypeError, ValueError):
        return None
    if every <= 0:
        return None
    with _lock:
        _state["steps"] += 1
        if _state["steps"] % every:
            return None
    return explore_one()


def explore_one() -> dict | None:
    """Probe the first unvisited candidate key for THIS device_kind.
    Unbuildable keys (op families without an arm builder, platform-gated
    kernels) are marked visited and skipped — explore never retries a key
    in-process, so a stuck candidate cannot eat every idle gap."""
    from .. import policy

    db = policy.get_db()
    dk = policy.device_kind()
    for key in sorted(db.entries):
        entry = db.entries[key]
        if entry.get("source") != "candidate":
            continue
        if not key.endswith("|" + dk):
            continue
        with _lock:
            if key in _state["done"]:
                continue
            _state["done"].add(key)
        out = _probe(db, key, entry)
        if out is not None:
            return out
    return None


def _probe(db, key: str, entry: dict) -> dict | None:
    from .. import policy

    parts = key.split("|")
    if len(parts) != 4:
        return None
    op, shape_key, dtype, _dev = parts
    field = features.decision_field(op)
    if field is None:
        return None
    arms = _build_arms(op, shape_key, dtype)
    if not arms or len(arms) < 2:
        return None
    measured = {a: _measure(arms[a]) for a in sorted(arms)}
    store.record_measured(key, measured, source="explore")
    base = str(entry.get("decision", {}).get(field, ""))
    if base not in measured:
        base = sorted(measured)[0]
    best = min(sorted(measured), key=lambda a: measured[a]["median_s"])
    band = max([EXPLORE_BAND] + [m["band"] for m in measured.values()])
    verdict = _verdict(measured[base]["median_s"],
                       measured[best]["median_s"], band) \
        if best != base else "retire"
    path = str(flags.get_flag("tuning_db")).strip()
    if verdict == "tie":
        # inside the band: the analytic candidate stands, but now with
        # measured evidence attached (the db.py satellite fix — candidates
        # carry times when available)
        db.put(key, entry.get("decision", {}), source="candidate",
               measured=evidence(measured),
               note="explore: tie inside band")
        result = {"key": key, "verdict": "tie", "decision": None}
    else:
        winner = best if verdict == "keep" else base
        db.put(key, {field: winner}, source="swept",
               measured=evidence(measured),
               note=f"explore: verdict={verdict} base={base}")
        _bump_promotion(op)
        result = {"key": key, "verdict": verdict, "decision": winner}
    if path:
        try:
            db.save(path)
            policy.invalidate_db_cache()
        except OSError:
            pass  # read-only FS: the in-memory entry still serves
    result["measured"] = {a: m["median_s"] for a, m in measured.items()}
    return result


def _bump_promotion(op: str) -> None:
    from . import bump_promotion

    bump_promotion(op)


def _verdict(base_s: float, cand_s: float, band: float) -> str:
    if cand_s < (1.0 - band) * base_s:
        return "keep"
    if cand_s > (1.0 + band) * base_s:
        return "retire"
    return "tie"


def _measure(fn) -> dict:
    """Tiny bounded version of tools/_timing.measure: one warmup call
    (compile), then EXPLORE_PASSES windows of EXPLORE_ITERS calls each."""
    import jax

    jax.block_until_ready(fn())
    windows = []
    for _ in range(EXPLORE_PASSES):
        t0 = time.perf_counter()
        out = None
        for _ in range(EXPLORE_ITERS):
            out = fn()
        jax.block_until_ready(out)
        windows.append((time.perf_counter() - t0) / EXPLORE_ITERS)
    ws = np.asarray(windows, dtype=np.float64)
    med = float(np.median(ws))
    return {
        "median_s": med,
        "min_s": float(ws.min()),
        "windows_s": [round(float(w), 9) for w in windows],
        "band": round(float((ws.max() - ws.min()) / med), 4)
        if med > 0 else 0.0,
    }


def _build_arms(op: str, shape_key: str, dtype: str) -> dict | None:
    """Reconstruct the timed arms for one candidate key — the same
    fwd+bwd jitted closures tools/tune.py sweeps, rebuilt from the key
    alone. Families explore cannot rebuild (paged decode needs a live KV
    pool; epilogue arms are platform-gated) return None and are
    skipped — offline sweeps remain their path to a verdict."""
    kv = features.parse_shape_key(op, shape_key)
    if kv is None:
        return None
    try:
        if op == "conv2d":
            return _conv_arms(kv, dtype)
        if op == "attention" and kv.get("sq", 0) > 1 \
                and kv.get("sq") == kv.get("sk"):
            return _attention_arms(kv, dtype)
    except Exception:
        return None  # an unbuildable arm must never crash the train loop
    return None


def _conv_arms(kv: dict, dtype: str) -> dict | None:
    import jax
    import jax.numpy as jnp

    from ...ops.nn_ops import _conv2d_igemm_f32

    n, (hout, wout) = kv["n"], kv["out"]
    cin, cout = kv["cin"], kv["cout"]
    kh, kw = kv["k"]
    strides, d = kv.get("s", (1, 1)), kv.get("d", (1, 1))
    fmt = kv.get("fmt", "NHWC")
    if fmt not in ("NHWC", "NCHW"):
        return None
    # any VALID-padded input reproducing the keyed output tile times the
    # same GEMM (the key deliberately forgets the padding)
    h = (hout - 1) * strides[0] + (kh - 1) * d[0] + 1
    w = (wout - 1) * strides[1] + (kw - 1) * d[1] + 1
    pads = ((0, 0), (0, 0))
    rhs = "HWIO" if fmt == "NHWC" else "OIHW"
    rng = np.random.default_rng(0)
    x_shape = (n, h, w, cin) if fmt == "NHWC" else (n, cin, h, w)
    w_shape = (kh, kw, cin, cout) if fmt == "NHWC" else (cout, cin, kh, kw)
    x = jax.device_put(rng.standard_normal(
        x_shape, dtype=np.float32).astype(dtype))
    wt = jax.device_put((rng.standard_normal(
        w_shape, dtype=np.float32) * 0.05).astype(dtype))

    def loss_direct(xx, ww):
        out = jax.lax.conv_general_dilated(
            xx, ww, window_strides=strides, padding=pads,
            rhs_dilation=d, dimension_numbers=(fmt, rhs, fmt))
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    def loss_igemm(xx, ww):
        return jnp.sum(jnp.square(
            _conv2d_igemm_f32(xx, ww, strides, pads, d, fmt)))

    f_direct = jax.jit(jax.grad(loss_direct, argnums=(0, 1)))
    f_igemm = jax.jit(jax.grad(loss_igemm, argnums=(0, 1)))
    return {"direct": lambda: f_direct(x, wt)[1],
            "igemm": lambda: f_igemm(x, wt)[1]}


def _attention_arms(kv: dict, dtype: str) -> dict | None:
    import jax
    import jax.numpy as jnp

    from ...ops.attention_ops import (_flash_bundled_ok, _pallas_short128_ok,
                                      _pallas_short_ok, _reference_attention)

    b, nh, s, dh = kv["b"], kv["nh"], kv["sq"], kv["dh"]
    causal = bool(kv.get("causal", 0))
    rng = np.random.default_rng(0)
    q, k, v = (jax.device_put(rng.standard_normal(
        (b, nh, s, dh), dtype=np.float32).astype(dtype)) for _ in range(3))
    sm = dh ** -0.5

    def mk(attn_fn):
        def loss(qq, kk, vv):
            return jnp.sum(jnp.square(
                attn_fn(qq, kk, vv).astype(jnp.float32)))
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return lambda: g(q, k, v)[0]

    arms = {"xla": mk(lambda qq, kk, vv: _reference_attention(
        qq, kk, vv, None, causal, sm))}
    if _pallas_short_ok(q.shape, k.shape, None):
        from ...ops.pallas_kernels import attention as psa

        arms["pallas_short"] = mk(lambda qq, kk, vv: psa.short_seq_attention(
            qq, kk, vv, causal=causal, sm_scale=sm))
    if _pallas_short128_ok(q.shape, k.shape, None):
        from ...ops.pallas_kernels import short_attention as s128

        arms["pallas_short128"] = mk(
            lambda qq, kk, vv: s128.short128_attention(
                qq, kk, vv, causal=causal, sm_scale=sm))
    if _flash_bundled_ok(q.shape, k.shape, q.dtype):
        from jax.experimental.pallas.ops.tpu import flash_attention as fa

        arms["flash_bundled"] = mk(lambda qq, kk, vv: fa.flash_attention(
            qq, kk, vv, causal=causal, sm_scale=sm))
    return arms
