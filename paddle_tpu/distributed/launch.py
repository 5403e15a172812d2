"""`python -m paddle_tpu.distributed.launch` — multi-process job launcher.

TPU-native re-design of the reference launcher
(/root/reference/python/paddle/distributed/launch.py: start_procs:132,
launch:243): same job shape — spawn one training process per device group,
wire the rank/endpoint env contract, multiplex logs, propagate failures — but
rendezvous is the PjRt coordination service (see distributed/parallel.py), not
a trainer-0 socket broadcast of an ncclUniqueId.

Usage:
    python -m paddle_tpu.distributed.launch --nproc_per_node=2 \
        [--backend=cpu --local_devices_per_proc=1] \
        [--log_dir=log] train.py --your --args

Each worker process receives:
    PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM   rank / world size
    PADDLE_COORDINATOR                        coordination service address
    PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT (fleet role makers)
    PADDLE_DIST_BACKEND / PADDLE_LOCAL_DEVICES (optional platform pinning)
and calls `paddle_tpu.distributed.init_parallel_env()` before building its
program (fleet.init with PaddleCloudRoleMaker picks up the same envs).
"""
from __future__ import annotations

import argparse
import glob
import os
import secrets
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _refuse_shared_chips(n_children: int, backend: str | None) -> None:
    """A TPU chip belongs to one process: with several children and no CPU
    pin, every child inherits every local chip and the second one to
    initialise fails or hangs. One process drives all local chips (a mesh
    over jax.devices()), so that combination is refused up front. Chips are
    detected by their device nodes — the launcher itself stays off jax."""
    backend = (backend or os.environ.get("PADDLE_DIST_BACKEND")
               or os.environ.get("JAX_PLATFORMS") or "")
    if n_children <= 1 or backend.split(",")[0].strip().lower() == "cpu":
        return
    chips = glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")
    if chips:
        raise SystemExit(
            f"paddle_tpu.distributed.launch: refusing to start {n_children} "
            f"processes on a host with TPU chips ({len(chips)} device "
            "node(s)): a chip belongs to ONE process, so the second child "
            "to initialise would fail or hang. One process drives all "
            "local chips — run the script directly and build its mesh over "
            "jax.devices() — or pass --backend=cpu for a CPU-only job.")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _parse_args(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch a multi-process distributed job",
    )
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes to spawn on this node")
    p.add_argument("--node_ip", default="127.0.0.1",
                   help="this node's IP (reference launch.py --node_ip)")
    p.add_argument("--coordinator", default=None,
                   help="coordination-service address host:port "
                        "(default: node_ip with a free port, single-node)")
    p.add_argument("--started_port", type=int, default=None,
                   help="base port for PADDLE_TRAINER_ENDPOINTS")
    p.add_argument("--backend", default=None,
                   help="pin jax platform in workers (e.g. 'cpu' for the "
                        "TestDistBase localhost pattern)")
    p.add_argument("--local_devices_per_proc", type=int, default=None,
                   help="virtual host devices per process (CPU backend)")
    p.add_argument("--log_dir", default=None,
                   help="write per-worker logs to LOG_DIR/workerlog.N")
    p.add_argument("--server_num", type=int, default=0,
                   help="parameter-server mode: spawn this many pservers "
                        "first (reference launch_ps.py --server_num)")
    p.add_argument("--worker_num", type=int, default=0,
                   help="parameter-server mode: trainer count "
                        "(reference launch_ps.py --worker_num)")
    p.add_argument("--servers", default=None,
                   help="explicit pserver endpoint list ip:port,ip:port "
                        "(default: node_ip with free ports)")
    p.add_argument("training_script", help="the script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def launch_ps(args) -> int:
    """Parameter-server cluster launcher (reference
    python/paddle/distributed/launch_ps.py:55-82 start_procs): spawn
    --server_num pservers, then --worker_num trainers, all running the SAME
    training script; roles arrive via TRAINING_ROLE/PADDLE_* envs that the
    fleet RoleMakers (incubate/fleet/base.py PaddleCloudRoleMaker) read.
    Returns when every trainer exits (pservers are then terminated, matching
    the reference's procs[i].proc.terminate() for servers)."""
    n_servers = args.server_num
    n_workers = args.worker_num or 1
    _refuse_shared_chips(n_servers + n_workers, args.backend)
    if args.servers:
        server_eps = [e for e in args.servers.split(",") if e]
        if args.server_num and len(server_eps) != args.server_num:
            raise ValueError(
                f"--servers lists {len(server_eps)} endpoints but "
                f"--server_num={args.server_num}; drop one or make them "
                "agree (one local pserver process is spawned per endpoint)")
        loopback = {"127.0.0.1", "localhost", "::1"}
        remote = [ep for ep in server_eps
                  if ep.rsplit(":", 1)[0] not in loopback]
        if remote and not os.environ.get("PADDLE_PS_AUTHKEY"):
            # the per-launch generated secret only reaches THIS node's
            # children; processes launched on the other nodes would hold a
            # different key and every cross-node connect would die with an
            # opaque multiprocessing AuthenticationError
            raise RuntimeError(
                f"--servers includes non-local endpoint(s) {remote} but "
                "PADDLE_PS_AUTHKEY is not set. Cross-node pserver RPC "
                "authenticates with one shared secret: export the same "
                "PADDLE_PS_AUTHKEY (e.g. `export PADDLE_PS_AUTHKEY=$(openssl "
                "rand -hex 16)`) on every node before launching")
    else:
        server_eps = [f"{args.node_ip}:{_free_port()}"
                      for _ in range(n_servers)]
    base_port = args.started_port or _free_port()
    trainer_eps = [f"{args.node_ip}:{base_port + i}" for i in range(n_workers)]
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    ps_authkey = os.environ.get("PADDLE_PS_AUTHKEY") or secrets.token_hex(16)

    common = {
        "PADDLE_PS_AUTHKEY": ps_authkey,
        "PADDLE_PSERVERS_IP_PORT_LIST": ",".join(server_eps),
        "PADDLE_PSERVER_ENDPOINTS": ",".join(server_eps),
        "PADDLE_TRAINERS_NUM": str(n_workers),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(trainer_eps),
    }
    if args.backend:
        common["PADDLE_DIST_BACKEND"] = args.backend

    def _spawn(role_env, tag):
        env = dict(os.environ)
        env.update(common)
        env.update(role_env)
        out = None
        if args.log_dir:
            out = open(os.path.join(args.log_dir, f"{tag}.log"), "w")
            logs.append(out)
        cmd = [sys.executable, "-u", args.training_script,
               *args.training_script_args]
        return subprocess.Popen(cmd, env=env, stdout=out, stderr=out)

    logs: list = []
    servers = [
        _spawn({"TRAINING_ROLE": "PSERVER", "PADDLE_PSERVER_ID": str(i),
                "PADDLE_CURRENT_ENDPOINT": ep, "PADDLE_PORT": ep.rsplit(":", 1)[1],
                "POD_IP": ep.rsplit(":", 1)[0]}, f"serverlog.{i}")
        for i, ep in enumerate(server_eps)
    ]
    workers = [
        _spawn({"TRAINING_ROLE": "TRAINER", "PADDLE_TRAINER_ID": str(i),
                "PADDLE_CURRENT_ENDPOINT": trainer_eps[i]}, f"workerlog.{i}")
        for i in range(n_workers)
    ]

    rc = 0
    try:
        # poll loop (same discipline as the collective launch() below): one
        # crashed trainer must tear the whole job down — a sequential wait()
        # would hang forever on the surviving trainers' barriers
        alive = set(range(n_workers))
        while alive:
            for i in list(alive):
                r = workers[i].poll()
                if r is None:
                    continue
                alive.discard(i)
                if r != 0:
                    rc = r
                    for w in workers:
                        if w.poll() is None:
                            w.send_signal(signal.SIGTERM)
                    alive.clear()
            time.sleep(0.1)
        # trainers done (or failed): tear the servers down
        stop = list(servers) + ([w for w in workers if w.poll() is None]
                                if rc else [])
        for s in stop:
            if s.poll() is None:
                s.send_signal(signal.SIGTERM)
        deadline = time.time() + 10
        for s in stop:
            try:
                s.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                s.kill()
    finally:
        for f in logs:
            f.close()
    return rc


def launch(args) -> int:
    if args.server_num or args.worker_num:
        return launch_ps(args)
    n = args.nproc_per_node
    _refuse_shared_chips(n, args.backend)
    coordinator = args.coordinator or f"{args.node_ip}:{_free_port()}"
    base_port = args.started_port or _free_port()
    endpoints = [f"{args.node_ip}:{base_port + i}" for i in range(n)]

    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    # one random pserver-RPC auth secret per launch, shared by every rank
    ps_authkey = os.environ.get("PADDLE_PS_AUTHKEY") or secrets.token_hex(16)

    procs, logs = [], []
    for rank in range(n):
        env = dict(os.environ)
        env.update({
            "PADDLE_PS_AUTHKEY": ps_authkey,
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(n),
            "PADDLE_COORDINATOR": coordinator,
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "TRAINING_ROLE": "TRAINER",
        })
        if args.backend:
            env["PADDLE_DIST_BACKEND"] = args.backend
        if args.local_devices_per_proc:
            env["PADDLE_LOCAL_DEVICES"] = str(args.local_devices_per_proc)
        cmd = [sys.executable, "-u", args.training_script,
               *args.training_script_args]
        out = None
        if args.log_dir:
            out = open(os.path.join(args.log_dir, f"workerlog.{rank}"), "w")
            logs.append(out)
        procs.append(subprocess.Popen(cmd, env=env, stdout=out, stderr=out))

    rc = 0
    try:
        alive = set(range(n))
        while alive:
            for i in list(alive):
                r = procs[i].poll()
                if r is None:
                    continue
                alive.discard(i)
                if r != 0:
                    rc = r
                    # one worker died: the pod step can never complete — tear
                    # the job down (reference launch.py terminate_procs)
                    for j in alive:
                        procs[j].send_signal(signal.SIGTERM)
                    deadline = time.time() + 10
                    for j in alive:
                        try:
                            procs[j].wait(max(0.1, deadline - time.time()))
                        except subprocess.TimeoutExpired:
                            procs[j].kill()
                    alive.clear()
            time.sleep(0.1)
    finally:
        for f in logs:
            f.close()
    return rc


def main(argv=None):
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    sys.exit(launch(args))


if __name__ == "__main__":
    main()
