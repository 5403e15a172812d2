"""Parameter-server variable RPC: client + server runtime.

TPU-native replacement for the reference RPC stack:
  * `RPCClient` contract (rpc_client.h:33: AsyncSendVar :37, AsyncGetVar :43,
    barriers :68-74) -> `PSClient` (send_var/get_var/send_barrier/
    fetch_barrier/send_complete)
  * `listen_and_serv` event loop (distributed_ops/listen_and_serv_op.cc) +
    RequestSend/Get handlers (request_handler_impl.cc) -> `PServerRuntime`
  * gRPC ByteBuffer serde (grpc/grpc_serde.cc) -> a length-prefixed raw
    tensor frame over `multiprocessing.connection` byte pipes: a small JSON
    meta header (op, name, trainer, dtype/shape table) followed by the raw
    tensor bytes, decoded with zero-copy np.frombuffer views. No pickle on
    the wire — version-stable and copy-light, the same serde discipline as
    the reference's zero-copy gRPC ByteBuffer path. The connection-level
    HMAC challenge (authkey) is kept for transport auth.

Sync semantics (sync_mode=True): the server buffers each trainer's gradient
per variable; when every trainer has posted its send_barrier, gradients are
averaged, the per-block optimize programs run once, the global step++, and
only then are the barrier replies released — so a subsequent get_var always
observes the post-update parameters (the reference's send_barrier/
fetch_barrier protocol collapsed into one blocking round).

Liveness (the distributed hang defense):
  * every RPC reply wait and the connect loop are bounded by
    `FLAGS_rpc_deadline` (ms, reference semantics) — no hardcoded timeouts;
  * each trainer runs a heartbeat daemon thread (`_HeartbeatSender`, its own
    connections so a blocking barrier can't delay a beat) that refreshes the
    server's per-trainer `last_seen` clock;
  * a server-side monitor thread watches stalled sync rounds: a trainer that
    is holding the barrier hostage with no liveness signal for the deadline
    is EVICTED — its half-round gradients are dropped, the barrier count
    renormalizes to the survivors, the round runs, and the eviction is
    logged (`PServerRuntime.liveness_log`) — instead of blocking everyone;
  * an evicted trainer that comes back (an explicit `rejoin` RPC from a
    restarted process resuming at CheckpointManager.latest_step, or simply
    its next send/barrier if it was a false positive) is re-admitted at the
    next barrier accounting, and the server grants evicted trainers a
    rejoin-grace window before it will shut down without them."""
from __future__ import annotations

import logging
import threading
import time
from multiprocessing.connection import Client, Listener
from typing import Any

import numpy as np

logger = logging.getLogger("paddle_tpu.distributed.ps_rpc")

# how long a server that is shutting down waits for its own threads
THREAD_DRAIN_S = 5.0


def rpc_deadline_s() -> float:
    """`FLAGS_rpc_deadline` (milliseconds, reference
    fluid/__init__.py:65-71 semantics) as seconds; floor 1ms."""
    from .. import flags

    try:
        ms = float(flags.get_flag("rpc_deadline"))
    except KeyError:  # flags module mid-import
        ms = 180000.0
    return max(ms, 1.0) / 1000.0


def heartbeat_timeout_s() -> float:
    """Server-side liveness deadline: `FLAGS_heartbeat_timeout_ms`, falling
    back to the RPC deadline when unset (0)."""
    from .. import flags

    try:
        ms = float(flags.get_flag("heartbeat_timeout_ms"))
    except KeyError:
        ms = 0.0
    return ms / 1000.0 if ms > 0 else rpc_deadline_s()

def _authkey() -> bytes:
    """Connection auth secret. The launcher exports PADDLE_PS_AUTHKEY (one
    random value per launch) so all ranks share it; a hand-run cluster must
    export it itself. The fallback keeps single-process tests working but is
    NOT a security boundary."""
    import os

    return os.environ.get("PADDLE_PS_AUTHKEY", "paddle_tpu_ps").encode()


def _parse_ep(ep: str):
    host, port = ep.rsplit(":", 1)
    return (host, int(port))


# -- wire frame: JSON meta + raw tensor blocks --------------------------------
# frame := u32(meta_len) meta_json tensor_bytes*
# meta["_t"] = [[dtype_str, shape], ...] describes the appended raw blocks in
# order; everything else in meta is small scalars/strings. send_bytes adds the
# outer length prefix. SelectedRows travel as two blocks (rows, values) plus
# a "height" field; replies are {"s": "ok"|"err", ...} frames.

import json as _json
import struct as _struct


def _pack(meta: dict, tensors=()) -> bytes:
    tensors = [np.asarray(t) for t in tensors]
    meta = dict(meta)
    # shapes recorded BEFORE ascontiguousarray (it promotes 0-d to 1-d)
    meta["_t"] = [[t.dtype.str, list(t.shape)] for t in tensors]
    mb = _json.dumps(meta, separators=(",", ":")).encode()
    parts = [_struct.pack("<I", len(mb)), mb]
    parts += [memoryview(np.ascontiguousarray(t)).cast("B") for t in tensors]
    return b"".join(parts)


def _unpack(buf):
    (mlen,) = _struct.unpack_from("<I", buf, 0)
    meta = _json.loads(bytes(buf[4:4 + mlen]).decode())
    off = 4 + mlen
    tensors = []
    for dtype_str, shape in meta.pop("_t", []):
        dt = np.dtype(dtype_str)
        n = int(np.prod(shape)) if shape else 1
        t = np.frombuffer(buf, dt, count=n, offset=off).reshape(tuple(shape))
        off += n * dt.itemsize
        tensors.append(t)
    return meta, tensors


def _reply_ok(conn, tensors=(), **fields):
    conn.send_bytes(_pack({"s": "ok", **fields}, tensors))


def _reply_err(conn, msg: str):
    conn.send_bytes(_pack({"s": "err", "msg": msg}))


# -- wire contract for row-sliced variables ----------------------------------
# One definition of the "name.block{j}" section protocol shared by the send/
# recv ops AND the async Communicator — the slicing math must never drift
# between the three users (reference parameter_send.cc / parameter_recv.cc).


def iter_sections(name: str, arr, epmap, sections):
    """The one definition of the row-split wire protocol: yields
    (endpoint, wire_name, row_slice). EMPTY sections = unsliced whole var
    under its bare name; NON-empty (even a single block) = the server
    registered "name.block{j}" wire names."""
    if not sections:
        yield epmap[0], name, arr
        return
    offs = np.cumsum([0] + list(sections[:-1]))
    for j, (ep, off, rows) in enumerate(zip(epmap, offs, sections)):
        yield ep, f"{name}.block{j}", arr[off:off + rows]


def _guard_drops_send(name: str, arr) -> bool:
    """Trainer-side numeric hygiene (FLAGS_guard_numerics, resilience/
    guardrails.py): a non-finite payload is dropped BEFORE the wire so the
    pserver never averages poison into shared parameters. The sync server
    renormalizes the round to the trainers that posted (_run_round), the
    same stance as PR 3's dead-trainer eviction."""
    from .. import flags, profiler

    if not flags.get_flag("guard_numerics"):
        return False
    a = np.asarray(arr)
    if a.dtype.kind != "f" or np.isfinite(a).all():
        return False
    profiler.bump("ps.nonfinite_drop")
    print(f"[ps_rpc] dropping non-finite send '{name}' "
          f"(FLAGS_guard_numerics fleet hygiene)", flush=True)
    return True


def send_sections(client, name: str, arr, epmap, sections) -> None:
    if _guard_drops_send(name, arr):
        return
    for ep, wire, part in iter_sections(name, arr, epmap, sections):
        client.send_var(ep, wire, part)


def fetch_sections(client, name: str, epmap, sections) -> np.ndarray:
    """Inverse of send_sections: pull + row-concat a var's blocks."""
    if not sections:
        return client.get_var(epmap[0], name)
    parts = [client.get_var(ep, f"{name}.block{j}")
             for j, ep in enumerate(epmap)]
    return np.concatenate(parts, axis=0)


def send_sparse_sections(client, name: str, sr, epmap, begins,
                         sections) -> None:
    """Route a SelectedRows grad to its row-owning servers with slice-LOCAL
    indices (reference split_ids + parameter_send.cc SelectedRows path).
    Empty sections = whole table on epmap[0], global rows as-is."""
    from ..core.selected_rows import SelectedRows

    if _guard_drops_send(name, sr.values):
        return
    if not sections:
        client.send_var(epmap[0], name, sr)
        return
    rows = np.asarray(sr.rows)
    vals = np.asarray(sr.values)
    for j, (ep, b, s) in enumerate(zip(epmap, begins, sections)):
        mask = (rows >= b) & (rows < b + s)
        if not mask.any():
            continue
        client.send_var(ep, f"{name}.block{j}",
                        SelectedRows(rows[mask] - b, vals[mask], s))


class _HeartbeatSender(threading.Thread):
    """Per-client liveness beacon: a daemon thread sending `hb` frames to
    every pserver at FLAGS_heartbeat_interval_ms over its OWN connections —
    a blocking sync-barrier RPC holds the shared connection's lock for the
    whole round, so beats must never ride that socket. A beat's reply
    carries the server's eviction verdict for this trainer (surfaced via
    PSClient.was_evicted so a partitioned-but-alive trainer can notice and
    rejoin)."""

    def __init__(self, client: "PSClient", interval_s: float):
        super().__init__(daemon=True,
                         name=f"ps-heartbeat-{client.trainer_id}")
        self.client = client
        self.interval = float(interval_s)
        self.stop_event = threading.Event()
        self.evicted = threading.Event()
        self._conns: dict[str, Any] = {}

    def run(self):
        from ..resilience.faults import InjectedFault, fault_point

        while not self.stop_event.wait(self.interval):
            try:
                fault_point("heartbeat_loss")
            except InjectedFault:
                continue  # this beat is lost on the (simulated) floor
            for ep in self.client.endpoints:
                if self.stop_event.is_set():
                    return
                self._beat(ep)

    def _beat(self, ep: str):
        try:
            conn = self._conns.get(ep)
            if conn is None:
                conn = self._conns[ep] = Client(_parse_ep(ep),
                                                authkey=_authkey())
            conn.send_bytes(_pack({"op": "hb",
                                   "trainer": self.client.trainer_id}))
            if not conn.poll(max(self.interval, 1.0)):
                raise TimeoutError("heartbeat reply timed out")
            meta, _ = _unpack(conn.recv_bytes())
            if meta.get("evicted"):
                self.evicted.set()
        except Exception:
            # a sick endpoint only costs its own beat; redial next tick
            conn = self._conns.pop(ep, None)
            if conn is not None:
                try:
                    conn.close()
                except Exception:
                    pass

    def stop(self):
        self.stop_event.set()
        # a snapshot: the beat thread may still be dialing its first
        # connections into the dict
        for conn in list(self._conns.values()):
            try:
                conn.close()
            except Exception:
                pass
        self._conns.clear()


class PSClient:
    """One connection per pserver endpoint; thread-safe via a lock per conn."""

    _instances: dict[tuple, "PSClient"] = {}

    def __init__(self, endpoints: list[str], trainer_id: int):
        self.endpoints = list(endpoints)
        self.trainer_id = trainer_id
        self._conns = {}
        self._locks = {}
        # guards first-connection creation: the async Communicator calls in
        # from N send threads + the recv thread concurrently, and an
        # unsynchronized check-then-create could hand two threads the same
        # Connection under different locks
        self._create_lock = threading.Lock()
        self._retry = None  # lazy RetryPolicy (resilience/retry.py)
        self._hb: _HeartbeatSender | None = None

    def _policy(self):
        if self._retry is None:
            from ..resilience.retry import rpc_policy

            self._retry = rpc_policy()
        return self._retry

    def _drop_conn(self, ep: str) -> None:
        """Forget a (possibly broken) connection so the next RPC redials."""
        with self._create_lock:
            lock = self._locks.setdefault(ep, threading.Lock())
        with lock:
            conn = self._conns.pop(ep, None)
            if conn is not None:
                try:
                    conn.close()
                except Exception:
                    pass

    @classmethod
    def get(cls, endpoints, trainer_id) -> "PSClient":
        key = (tuple(endpoints), trainer_id)
        inst = cls._instances.get(key)
        if inst is None:
            inst = cls._instances[key] = cls(endpoints, trainer_id)
        return inst

    def _conn(self, ep: str):
        # the global lock only guards per-endpoint lock creation; the
        # (FLAGS_rpc_deadline-bounded) connect-retry runs under the
        # ENDPOINT's lock so one unreachable server cannot stall RPCs to
        # healthy ones
        with self._create_lock:
            lock = self._locks.setdefault(ep, threading.Lock())
        with lock:
            if ep not in self._conns:
                from ..resilience.retry import connect_policy

                def _dial():
                    self._conns[ep] = Client(_parse_ep(ep),
                                             authkey=_authkey())

                # flat-interval, FLAGS_rpc_deadline-bounded dial (the
                # server may still be starting) through the shared policy
                connect_policy().call(_dial)
        return self._conns[ep], lock

    def _call(self, ep: str, meta: dict, tensors=(), timeout=None):
        """One framed request/reply round; returns (meta, tensors).

        The reply wait is bounded: `timeout` seconds when given, else
        FLAGS_rpc_deadline — a dead server raises TimeoutError (transient,
        so the retrying callers redial) instead of blocking forever."""
        from ..resilience.faults import fault_point

        fault_point("rpc_drop")
        if timeout is None:
            timeout = rpc_deadline_s()
        conn, lock = self._conn(ep)
        with lock:
            conn.send_bytes(_pack(meta, tensors))
            if timeout and timeout > 0 and not conn.poll(timeout):
                # a late reply would desync the next RPC's framing — forget
                # the conn (inline: we already hold this endpoint's lock,
                # _drop_conn would deadlock re-acquiring it)
                self._conns.pop(ep, None)
                try:
                    conn.close()
                except Exception:
                    pass
                raise TimeoutError(
                    f"pserver {ep}: no reply to '{meta.get('op')}' within "
                    f"{timeout:.3g}s (FLAGS_rpc_deadline)")
            buf = conn.recv_bytes()
        rmeta, rtensors = _unpack(buf)
        if rmeta.get("s") == "err":
            raise RuntimeError(f"pserver {ep}: {rmeta.get('msg')}")
        return rmeta, rtensors

    # -- RPCClient contract --------------------------------------------------
    # Transient transport failures retry under the resilience rpc_policy,
    # redialing the endpoint between attempts. Dense sends are idempotent
    # within a round (the server keeps last-per-trainer); a sparse re-send
    # after an ambiguous mid-reply failure can double rows — the same
    # at-least-once tradeoff the reference gRPC retry path accepts.
    def send_var(self, ep: str, name: str, value) -> None:
        from ..resilience.faults import fault_point

        if hasattr(value, "rows"):  # SelectedRows
            meta = {"op": "send", "name": name, "trainer": self.trainer_id,
                    "kind": "sparse", "height": int(value.height)}
            tensors = [np.asarray(value.rows), np.asarray(value.values)]
        else:
            meta = {"op": "send", "name": name, "trainer": self.trainer_id,
                    "kind": "dense"}
            tensors = [np.asarray(value)]

        def _do():
            fault_point("ps.send")
            self._call(ep, meta, tensors)

        self._policy().call(_do, on_retry=lambda a, e: self._drop_conn(ep))

    def get_var(self, ep: str, name: str) -> np.ndarray:
        from ..resilience.faults import fault_point

        def _do():
            fault_point("ps.recv")
            _, (v,) = self._call(ep, {"op": "get", "name": name,
                                      "trainer": self.trainer_id})
            return v

        return self._policy().call(
            _do, on_retry=lambda a, e: self._drop_conn(ep))

    def prefetch(self, ep: str, name: str, ids) -> np.ndarray:
        """Fetch only the given (slice-local) rows of a server-resident
        table (reference RPCClient::AsyncPrefetchVar rpc_client.h:62 +
        RequestPrefetchHandler) — the whole table never travels."""
        def _do():
            _, (v,) = self._call(ep, {"op": "prefetch", "name": name},
                                 [np.asarray(ids, np.int64)])
            return v

        return self._policy().call(
            _do, on_retry=lambda a, e: self._drop_conn(ep))

    # -- liveness ------------------------------------------------------------
    def start_heartbeat(self) -> None:
        """Start the liveness beacon (idempotent; auto-invoked by the first
        send_barrier so every sync trainer heartbeats without API changes).
        FLAGS_heartbeat_interval_ms <= 0 disables."""
        if self._hb is not None and self._hb.is_alive():
            return
        from .. import flags

        interval_ms = int(flags.get_flag("heartbeat_interval_ms"))
        if interval_ms <= 0:
            return
        self._hb = _HeartbeatSender(self, interval_ms / 1000.0)
        self._hb.start()

    def stop_heartbeat(self) -> None:
        if self._hb is not None:
            self._hb.stop()
            self._hb = None

    @property
    def was_evicted(self) -> bool:
        """True once any heartbeat reply reported this trainer evicted."""
        return self._hb is not None and self._hb.evicted.is_set()

    def rejoin(self) -> int:
        """Ask every pserver to re-admit this trainer after an eviction (a
        restarted process calls this before resuming from its latest
        checkpoint). Returns the servers' max global step so the caller can
        log how far the survivors got while it was away."""
        step = 0
        for ep in self.endpoints:
            meta, _ = self._call(ep, {"op": "rejoin",
                                      "trainer": self.trainer_id})
            step = max(step, int(meta.get("step", 0)))
        if self._hb is not None:
            self._hb.evicted.clear()
        self.start_heartbeat()
        return step

    def send_barrier(self) -> None:
        """Blocks until the server has aggregated + applied this round.

        Bounded by 2x FLAGS_rpc_deadline, not 1x: the reply is legitimately
        gated on the server's own eviction deadline when a peer trainer
        died, so the client grants one extra deadline of grace before it
        gives up on the server itself."""
        import os

        from ..resilience.faults import InjectedFault, fault_point

        try:
            fault_point("trainer_crash")
        except InjectedFault:
            # the in-process stand-in for a mid-round SIGKILL: no cleanup,
            # no complete, heartbeats die with the process
            os._exit(137)
        self.start_heartbeat()
        timeout = 2.0 * rpc_deadline_s()
        for ep in self.endpoints:
            self._call(ep, {"op": "barrier", "trainer": self.trainer_id},
                       timeout=timeout)

    def fetch_barrier(self) -> None:
        pass  # subsumed: send_barrier only returns post-update

    def checkpoint_notify(self, dirname: str) -> None:
        """Ask every pserver to persist its parameter slices (reference
        checkpoint_notify_op.cc / RPCClient::AsyncCheckpointNotify): the
        server-side save means no slice ever travels back to the trainer."""
        for ep in self.endpoints:
            self._call(ep, {"op": "checkpoint", "dirname": dirname,
                            "trainer": self.trainer_id})

    def send_complete(self) -> None:
        self.stop_heartbeat()
        for ep in self.endpoints:
            try:
                self._call(ep, {"op": "complete", "trainer": self.trainer_id})
            except (EOFError, ConnectionError, TimeoutError, RuntimeError):
                pass

    def close(self):
        self.stop_heartbeat()
        for conn in self._conns.values():
            try:
                conn.close()
            except Exception:
                pass
        self._conns.clear()


class PServerRuntime:
    """The listen_and_serv event loop: owns a scope of parameter blocks and
    per-gradient optimize programs; serves send/get/barrier until every
    trainer sends `complete`."""

    def __init__(self, endpoint: str, n_trainers: int, sync_mode: bool,
                 blocks: list[dict], scope, executor,
                 dc_asgd: bool = False, dc_asgd_lambda: float = 1.0):
        """blocks: [{grad, param, optimize_program, sparse,
                     origin_param?, begin?, rows?}]"""
        self.endpoint = endpoint
        self.n_trainers = n_trainers
        self.sync_mode = sync_mode
        # delay-compensated async SGD (reference _append_dc_asgd_ops):
        # per-(grad, trainer) parameter snapshots for the compensation term
        self.dc_asgd = dc_asgd and not sync_mode
        self.dc_lambda = float(dc_asgd_lambda)
        self._param_bak: dict[tuple[str, int], np.ndarray] = {}
        self.blocks = {b["grad"]: b for b in blocks}
        self.scope = scope
        self.exe = executor
        # row-sliced params: carve this server's slice out of the full
        # startup-initialized value (reference get_startup_program splits
        # init ops; equal-seed init + slicing is equivalent)
        for b in blocks:
            rows = b.get("rows")
            if rows is not None and b["param"] != b.get("origin_param"):
                full = scope.find_var(b["origin_param"])
                if full is None:
                    raise RuntimeError(
                        f"pserver scope missing '{b['origin_param']}' — run "
                        f"the startup program first")
                begin = int(b.get("begin", 0))
                scope.set_var(b["param"],
                              np.asarray(full)[begin:begin + rows].copy())
        # delta payloads (geo-SGD) arrive under the PARAM wire name
        self._param_blocks = {b["param"]: b for b in blocks}
        self._lock = threading.Lock()
        self._grad_buf: dict[str, dict[int, Any]] = {}
        self._barrier_waiting: list = []
        self._barriers_seen: set[int] = set()
        self._completed: set[int] = set()
        self._step = 0
        self._shutdown = threading.Event()
        # -- liveness state (monitor thread + heartbeat handlers) -----------
        # invariant: _evicted and _completed stay disjoint
        self._last_seen: dict[int, float] = {}
        self._evicted: set[int] = set()
        self._round_started: float | None = None
        self._all_done_since: float | None = None
        self.liveness_log: list[dict] = []  # evict/rejoin forensic record

    # -- liveness ------------------------------------------------------------
    def _touch_locked(self, trainer) -> None:
        if trainer is not None:
            self._last_seen[int(trainer)] = time.monotonic()

    def _readmit_locked(self, trainer, how: str) -> None:
        """Re-admit an evicted trainer. Explicit `rejoin` RPCs land here, but
        so does an evicted trainer's next send/barrier — a false-positive
        eviction (e.g. a long GC pause) self-heals on its next round. Net
        barrier accounting stays consistent mid-round: readmission raises
        the active count by one exactly when the trainer re-enters the
        protocol."""
        t = int(trainer)
        if t not in self._evicted:
            return
        self._evicted.discard(t)
        self._all_done_since = None
        rec = {"event": "rejoin", "trainer": t,
               "step": self._step, "via": how}
        self.liveness_log.append(rec)
        # the print is load-bearing (tests grep the server subprocess's
        # stdout); the logger + registry carry the structured copies
        print(f"[ps_rpc] {self.endpoint}: trainer {t} rejoined via {how} "
              f"at step {self._step}", flush=True)
        logger.info("trainer %d rejoined via %s at step %d", t, how,
                    self._step, extra={"ps_liveness": rec})
        self._note_liveness(rec, "ps.rejoins")

    def _evict_locked(self, t: int, idle_s: float, timeout_s: float) -> None:
        self._evicted.add(t)
        # the dead trainer's half-round gradients must not leak into the
        # survivors' average (_run_round rescales to the active count)
        for buf in self._grad_buf.values():
            buf.pop(t, None)
        rec = {"event": "evict", "trainer": t, "step": self._step,
               "idle_s": round(idle_s, 3)}
        self.liveness_log.append(rec)
        print(f"[ps_rpc] {self.endpoint}: evicted trainer {t} from the "
              f"sync barrier at step {self._step} (no liveness signal for "
              f"{idle_s:.2f}s > {timeout_s:.2f}s deadline)", flush=True)
        logger.warning("evicted trainer %d at step %d (idle %.2fs > %.2fs)",
                       t, self._step, idle_s, timeout_s,
                       extra={"ps_liveness": rec})
        self._note_liveness(rec, "ps.evictions")

    def _note_liveness(self, rec: dict, counter: str) -> None:
        try:
            from .. import observability as obs

            obs.counter_inc(counter)
            obs.event("ps.liveness", rec, level="warning")
        except Exception:  # noqa: BLE001 — telemetry never stalls the server
            pass

    def _maybe_release_barrier_locked(self) -> bool:
        """Run the round and release every waiting trainer once the posted
        barriers cover all ACTIVE (not completed, not evicted) trainers."""
        if (not self._barriers_seen
                or len(self._barriers_seen) < self._active_trainers()):
            return False
        self._run_round()
        waiting, self._barrier_waiting = self._barrier_waiting, []
        self._barriers_seen = set()
        self._round_started = None
        for c in waiting:
            try:
                _reply_ok(c)
            except Exception:
                pass
        return True

    def _monitor_loop(self):
        """Liveness monitor: while a sync round is blocked, evict trainers
        whose last heartbeat/RPC (or, if never seen, the round's start) is
        older than the liveness deadline, then re-check barrier release.
        Also enforces the rejoin-grace shutdown so a permanently-dead
        trainer cannot make the server serve forever after everyone else
        completed."""
        while not self._shutdown.is_set():
            timeout = heartbeat_timeout_s()
            self._shutdown.wait(min(max(timeout / 4.0, 0.05), 1.0))
            if self._shutdown.is_set():
                return
            now = time.monotonic()
            shutdown = False
            with self._lock:
                if self._barrier_waiting and self._round_started is not None:
                    for t in range(self.n_trainers):
                        if (t in self._barriers_seen or t in self._completed
                                or t in self._evicted):
                            continue
                        # clamp to round start: eviction measures the stall,
                        # and a trainer that last spoke long before this
                        # round still gets one full deadline of it
                        seen = max(self._last_seen.get(t, 0.0),
                                   self._round_started)
                        idle = now - seen
                        if idle > timeout:
                            self._evict_locked(t, idle, timeout)
                    self._maybe_release_barrier_locked()
                remaining = (self.n_trainers - len(self._completed)
                             - len(self._evicted))
                if self._evicted and remaining <= 0 and self._completed:
                    if self._all_done_since is None:
                        self._all_done_since = now
                    elif now - self._all_done_since > max(10.0 * timeout,
                                                          60.0):
                        print(f"[ps_rpc] {self.endpoint}: evicted "
                              f"trainer(s) {sorted(self._evicted)} never "
                              f"rejoined within the grace window — "
                              f"shutting down", flush=True)
                        logger.warning(
                            "evicted trainer(s) %s never rejoined; shutting "
                            "down", sorted(self._evicted),
                            extra={"ps_liveness": {
                                "event": "grace_shutdown",
                                "evicted": sorted(self._evicted)}})
                        shutdown = True
                else:
                    self._all_done_since = None
            if shutdown:
                self._signal_shutdown()
                return

    # -- request handlers ----------------------------------------------------
    def _handle_send(self, msg):
        name = msg["name"]
        kind = msg["value"][0]
        with self._lock:
            self._touch_locked(msg.get("trainer"))
            if msg.get("trainer") is not None:
                self._readmit_locked(msg["trainer"], how="send")
            buf = self._grad_buf.setdefault(name, {})
            if kind == "sparse" and msg["trainer"] in buf:
                # accumulate repeated sparse sends within a round
                prev = buf[msg["trainer"]]
                buf[msg["trainer"]] = ("sparse",
                                       np.concatenate([prev[1], msg["value"][1]]),
                                       np.concatenate([prev[2], msg["value"][2]]),
                                       msg["value"][3])
            else:
                buf[msg["trainer"]] = msg["value"]
            if not self.sync_mode:
                self._apply_one(name)
        return True

    def _apply_one(self, grad_name):
        """Async mode: apply immediately with whatever arrived."""
        buf = self._grad_buf.get(grad_name, {})
        for tid in list(buf):
            self._apply_update(grad_name, [buf.pop(tid)], scale=1.0,
                               trainer=tid)

    def _handle_barrier(self, msg, conn):
        with self._lock:
            t = msg["trainer"]
            self._touch_locked(t)
            self._readmit_locked(t, how="barrier")
            if not self._barrier_waiting:
                self._round_started = time.monotonic()  # the stall clock
            self._barriers_seen.add(t)
            self._barrier_waiting.append(conn)
            if self._maybe_release_barrier_locked():
                return None  # replies already sent
        return "wait"  # reply deferred until the round completes

    def _active_trainers(self):
        return self.n_trainers - len(self._completed) - len(self._evicted)

    def _run_round(self):
        # sparse scales by the ACTIVE trainer count, not by how many posted:
        # a row-sharded sparse table legitimately gets rows from a subset of
        # trainers in a round, but the sync average is still over all of
        # them. Dense scales by the POSTED count (normally identical) so a
        # guardrail-dropped poisoned send renormalizes to the survivors.
        n_active = max(self._active_trainers(), 1)
        for grad_name, buf in list(self._grad_buf.items()):
            vals = [buf[t] for t in sorted(buf)]
            if not vals:
                continue
            if vals[0][0] == "sparse":
                scale = 1.0 / n_active
            else:
                # dense grads normally arrive from every active trainer; a
                # trainer that dropped a non-finite send (guardrails fleet
                # hygiene) simply doesn't post this round — renormalize the
                # average to the survivors, the same stance as the eviction
                # path's half-round drop (_evict_locked)
                scale = 1.0 / len(vals)
            self._apply_update(grad_name, vals, scale=scale)
            self._grad_buf[grad_name] = {}
        self._step += 1

    def _apply_update(self, grad_name, payloads, scale: float, trainer=None):
        from ..core.selected_rows import SelectedRows

        if payloads[0][0] == "delta":
            # geo-SGD payload: arrives under the PARAM wire name; the
            # server just ADDS it (reference GeoSgdCommunicator server
            # contract), no optimize program
            spec = self._param_blocks.get(grad_name)
            if spec is None:
                return
            param = np.asarray(self.scope.find_var(spec["param"]),
                               dtype=np.float32)
            for p in payloads:
                param = param + np.asarray(p[1], np.float32)
            self.scope.set_var(spec["param"], param)
            return
        spec = self.blocks.get(grad_name)
        if spec is None:
            return

        if payloads[0][0] == "sparse":
            rows = np.concatenate([p[1] for p in payloads])
            vals = np.concatenate([p[2] for p in payloads]) * scale
            grad = SelectedRows(rows, vals, payloads[0][3])
        else:
            acc = payloads[0][1].astype(np.float32).copy()
            for p in payloads[1:]:
                acc += p[1]
            grad = acc * scale
            if self.dc_asgd and trainer is not None:
                # reference _append_dc_asgd_ops: g_comp = g + lambda *
                # g*g*(param_now - param_bak[trainer]); the snapshot then
                # advances to the freshly updated param
                param = np.asarray(self.scope.find_var(spec["param"]),
                                   dtype=np.float32)
                bak = self._param_bak.get((grad_name, trainer))
                if bak is not None:
                    grad = grad + self.dc_lambda * grad * grad * (param - bak)
        from ..executor import scope_guard

        with scope_guard(self.scope):
            self.exe.run(spec["optimize_program"], feed={grad_name: grad})

    def _handle_checkpoint(self, msg):
        """Persist this server's slices (reference checkpoint_notify -> the
        pserver-side save in listen_and_serv). One npz per server endpoint;
        the load side is fleet.init_server(model_dir) (parameter_server.py).
        Written tmp-then-rename under the lock: concurrent notifies from
        several trainers must not interleave zip writes."""
        import os

        dirname = msg["dirname"]
        os.makedirs(dirname, exist_ok=True)
        safe_ep = self.endpoint.replace(":", "_").replace("/", "_")
        path = os.path.join(dirname, f"pserver-{safe_ep}.npz")
        with self._lock:
            arrays = {n: np.asarray(self.scope.find_var(n))
                      for n in self.scope.var_names()
                      if self.scope.find_var(n) is not None}
            # np.savez appends ".npz" when missing — keep the suffix so the
            # tmp name is exactly what gets written
            tmp = path + f".tmp{msg.get('trainer', 0)}.npz"
            np.savez(tmp, **arrays)
            os.replace(tmp, path)
        return path

    def _handle_get(self, msg):
        with self._lock:
            self._touch_locked(msg.get("trainer"))
            v = self.scope.find_var(msg["name"])
            if v is None:
                raise KeyError(f"pserver has no var '{msg['name']}'")
            out = np.asarray(v)
            if self.dc_asgd and "trainer" in msg:
                # DC-ASGD snapshots the param AT THE MOMENT THE TRAINER
                # SEES IT — compensation then measures exactly the updates
                # that trainer missed (snapshotting at apply time instead
                # would also count updates it had already observed)
                for spec in self.blocks.values():
                    if spec["param"] == msg["name"]:
                        self._param_bak[(spec["grad"], msg["trainer"])] = \
                            out.astype(np.float32).copy()
        return out

    def _handle_prefetch(self, msg):
        """Row-gather from a table slice (reference
        RequestPrefetchHandler::Handle running the table's lookup block).
        ids are slice-LOCAL (the trainer's prefetch op already subtracted
        the block's row offset)."""
        with self._lock:
            v = self.scope.find_var(msg["name"])
            if v is None:
                raise KeyError(f"pserver has no table '{msg['name']}'")
            table = np.asarray(v)
            ids = np.asarray(msg["ids"], np.int64)
            if ids.size and (ids.min() < 0 or ids.max() >= table.shape[0]):
                raise IndexError(
                    f"prefetch ids out of range for '{msg['name']}' "
                    f"[0, {table.shape[0]}): min={ids.min()} max={ids.max()}")
            return table[ids]

    # -- event loop ----------------------------------------------------------
    def _signal_shutdown(self):
        """Set the flag, then poke the listen socket: closing an fd does NOT
        wake a thread blocked in accept() on Linux, so serve() is nudged with
        a throwaway connection instead."""
        self._shutdown.set()
        import socket as _socket

        try:
            s = _socket.create_connection(_parse_ep(self.endpoint), timeout=1.0)
            s.close()
        except OSError:
            pass

    def _warm_optimize_programs(self):
        """Pre-compile each dense block's optimize program before accepting
        traffic: the first real send otherwise pays the whole-block jit
        compile while holding the server lock, stalling every trainer for
        seconds (observed: an async trainer finishes its run before the
        first update lands). A zero-grad run hits the same compile cache as
        real sends (same feed shape); the scope snapshot/restore makes it
        side-effect-free for any optimizer state."""
        from ..executor import scope_guard

        todo = [s for s in self.blocks.values()
                if not s.get("sparse")
                and self.scope.find_var(s["param"]) is not None]
        if not todo:
            return
        # ONE snapshot around all warmups, as HOST COPIES: the executor
        # donates state buffers into each run, so restoring the original
        # jax.Array references would put deleted buffers back into the scope
        snapshot = {}
        for k, v in self.scope._vars.items():
            try:
                snapshot[k] = np.array(np.asarray(v))
            except Exception:
                snapshot[k] = v  # non-array state: not donate-able
        try:
            for spec in todo:
                pv = self.scope.find_var(spec["param"])
                zero = np.zeros(np.asarray(pv).shape, np.float32)
                with scope_guard(self.scope):
                    self.exe.run(spec["optimize_program"],
                                 feed={spec["grad"]: zero})
        finally:
            self.scope._vars = snapshot

    def serve(self):
        import os

        host = _parse_ep(self.endpoint)[0]
        if (host not in ("127.0.0.1", "localhost", "::1")
                and not os.environ.get("PADDLE_PS_AUTHKEY")):
            # the built-in fallback authkey is not a boundary; a bind on a
            # routable address without an explicit launch secret would accept
            # writes from anything on the network
            raise RuntimeError(
                f"refusing to bind pserver on non-loopback '{self.endpoint}' "
                "with the default authkey — export PADDLE_PS_AUTHKEY (the "
                "launcher does this automatically)")
        self._warm_optimize_programs()
        listener = Listener(_parse_ep(self.endpoint), authkey=_authkey())
        monitor = threading.Thread(target=self._monitor_loop, daemon=True,
                                   name="ps-liveness-monitor")
        monitor.start()
        threads = [monitor]
        while not self._shutdown.is_set():
            try:
                conn = listener.accept()
            except (OSError, EOFError):
                if self._shutdown.is_set():
                    break
                raise  # a healthy listener doesn't fail accept — surface it
            except Exception:
                continue  # auth failure from a stray client: keep serving
            if self._shutdown.is_set():
                break
            t = threading.Thread(target=self._client_loop, args=(conn,),
                                 daemon=True)
            t.start()
            threads.append(t)
        try:
            listener.close()
        except OSError:
            pass
        # The runtime owns its threads: wait for them to end (the monitor
        # wakes on the flag, a trainer closes its connections right after
        # `complete`). One still alive here holds the runtime through its
        # target; if it dropped the LAST reference while the interpreter
        # shuts down, the executor's compiled programs would be destroyed
        # on a daemon thread that can no longer take the GIL, and the
        # process aborts ("FATAL: exception not rethrown").
        deadline = time.monotonic() + THREAD_DRAIN_S
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))

    def _client_loop(self, conn):
        while not self._shutdown.is_set():
            try:
                buf = conn.recv_bytes()
            except (EOFError, OSError):
                return
            try:
                msg, tensors = _unpack(buf)
                op = msg["op"]
                # reconstruct the handler-facing payload tuples from the raw
                # tensor blocks (frame kinds: dense/sparse/delta)
                if op == "send":
                    kind = msg["kind"]
                    if kind == "sparse":
                        msg["value"] = ("sparse", tensors[0], tensors[1],
                                        msg["height"])
                    else:
                        msg["value"] = (kind, tensors[0])
                    self._handle_send(msg)
                    _reply_ok(conn)
                elif op == "get":
                    _reply_ok(conn, [self._handle_get(msg)])
                elif op == "prefetch":
                    msg["ids"] = tensors[0]
                    _reply_ok(conn, [self._handle_prefetch(msg)])
                elif op == "barrier":
                    r = self._handle_barrier(msg, conn)
                    if r == "wait":
                        pass  # reply comes when the round completes
                elif op == "checkpoint":
                    _reply_ok(conn, path=self._handle_checkpoint(msg))
                elif op == "hb":
                    with self._lock:
                        self._touch_locked(msg["trainer"])
                        evicted = int(msg["trainer"]) in self._evicted
                    _reply_ok(conn, evicted=evicted)
                elif op == "rejoin":
                    with self._lock:
                        self._touch_locked(msg["trainer"])
                        # a restarted trainer trains again: it owes a fresh
                        # `complete`, so it cannot stay in the done set
                        self._completed.discard(int(msg["trainer"]))
                        self._readmit_locked(msg["trainer"], how="rejoin")
                        step = self._step
                    _reply_ok(conn, step=step)
                elif op == "complete":
                    with self._lock:
                        self._touch_locked(msg["trainer"])
                        self._completed.add(msg["trainer"])
                        self._evicted.discard(int(msg["trainer"]))
                        done = len(self._completed) >= self.n_trainers
                        # release any trainers stuck on the barrier
                        self._maybe_release_barrier_locked()
                    _reply_ok(conn)
                    if done:
                        self._signal_shutdown()
                        return
                else:
                    _reply_err(conn, f"unknown op {msg['op']}")
            except Exception as e:  # serve must not die on one bad request
                try:
                    _reply_err(conn, f"{type(e).__name__}: {e}")
                except Exception:
                    return


def send_delta_sections(client, name: str, delta, epmap, sections) -> None:
    """Geo-SGD push: ship an accumulated parameter DELTA under the PARAM
    wire name (server adds it, no optimizer). Shares iter_sections so the
    slicing math cannot drift from send_sections. NOT retried at this layer:
    the server ADDS deltas, so an ambiguous re-send would double-apply —
    geo's rebase-on-pull makes a lost push self-correcting instead."""
    if _guard_drops_send(name, delta):
        return
    for ep, wire, part in iter_sections(name, delta, epmap, sections):
        client._call(ep, {"op": "send", "name": wire,
                          "trainer": client.trainer_id, "kind": "delta"},
                     [np.asarray(part)])
