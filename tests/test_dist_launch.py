"""Launcher + multi-process collective training (reference
unittests/test_dist_base.py:442 TestDistBase pattern, collective/NCCL2 mode):
`python -m paddle_tpu.distributed.launch` over 2 localhost CPU processes must
reproduce the single-process full-batch parameter trajectory."""
import os
import subprocess
import sys

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)
_SCRIPT = os.path.join(_DIR, "dist_collective.py")


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the conftest pins XLA_FLAGS for the in-process suite; workers provision
    # their own device count via init_parallel_env
    env.pop("PADDLE_TRAINERS_NUM", None)
    env.pop("PADDLE_TRAINER_ID", None)
    return env


def test_launch_two_process_collective_matches_local(tmp_path):
    local_out = str(tmp_path / "local.npz")
    p = subprocess.run(
        [sys.executable, _SCRIPT, local_out],
        env=_env(), capture_output=True, timeout=300)
    assert p.returncode == 0, p.stderr.decode()[-3000:]

    log_dir = str(tmp_path / "log")
    dist_out = str(tmp_path / "dist")  # each rank writes dist.r{rank}.npz
    p = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--backend", "cpu",
         "--local_devices_per_proc", "1", "--log_dir", log_dir,
         _SCRIPT, dist_out],
        env=_env(), cwd=_REPO, capture_output=True, timeout=300)
    logs = ""
    for i in range(2):
        f = os.path.join(log_dir, f"workerlog.{i}")
        if os.path.exists(f):
            with open(f) as fh:
                logs += f"--- workerlog.{i}\n" + fh.read()[-3000:]
    assert p.returncode == 0, logs + p.stderr.decode()[-2000:]

    local = np.load(local_out)
    r0 = np.load(dist_out + ".r0.npz")
    r1 = np.load(dist_out + ".r1.npz")
    for k in local.files:
        if k == "__last_loss__":
            continue
        np.testing.assert_allclose(
            local[k], r0[k], rtol=1e-4, atol=1e-5,
            err_msg=f"param {k} diverged from local baseline")
        np.testing.assert_allclose(
            r0[k], r1[k], rtol=1e-6, atol=1e-7,
            err_msg=f"ranks disagree on param {k}")


def test_launch_ps_spawns_servers_and_workers(tmp_path):
    """`launch --server_num --worker_num` drives a real 2-server/2-trainer
    fleet job end-to-end: roles arrive via the exported PADDLE_* envs
    (reference launch_ps.py:55-82), trainers converge and agree (sync)."""
    script = os.path.join(_DIR, "dist_ps_launched.py")
    p = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--server_num=2", "--worker_num=2",
         f"--log_dir={tmp_path / 'logs'}", script, str(tmp_path)],
        env=_env(), capture_output=True, timeout=300)
    logs = ""
    logdir = tmp_path / "logs"
    if logdir.exists():
        for f in sorted(logdir.iterdir()):
            logs += f"\n--- {f.name} ---\n" + f.read_text()[-2000:]
    assert p.returncode == 0, (p.stdout.decode()[-1000:],
                               p.stderr.decode()[-1000:], logs[-6000:])
    t0 = np.load(tmp_path / "trainer0.npz")
    t1 = np.load(tmp_path / "trainer1.npz")
    losses = t0["__losses__"]
    assert losses[-1] < losses[0], losses
    for k in t0.files:
        if k.startswith("__"):
            continue
        np.testing.assert_allclose(t0[k], t1[k], rtol=1e-5, atol=1e-6)


def test_launch_refuses_several_processes_on_a_tpu_host(monkeypatch):
    """A chip belongs to one process: several children with no CPU pin on
    a host with TPU device nodes are refused; a CPU pin or one child is
    not."""
    import pytest

    from paddle_tpu.distributed import launch

    monkeypatch.delenv("PADDLE_DIST_BACKEND", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(
        launch.glob, "glob",
        lambda pat: ["/dev/vfio/0"] if pat.startswith("/dev/vfio") else [])
    with pytest.raises(SystemExit, match="ONE process"):
        launch._refuse_shared_chips(2, None)
    launch._refuse_shared_chips(1, None)
    launch._refuse_shared_chips(2, "cpu")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    launch._refuse_shared_chips(2, None)
