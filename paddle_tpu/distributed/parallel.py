"""Multi-process bootstrap: PjRt coordination instead of NCCL-ID rendezvous.

TPU-native replacement for the reference's multi-process plumbing:
  * gen_nccl_id RPC bootstrap
    (/root/reference/paddle/fluid/operators/distributed_ops/gen_nccl_id_op.cc:76)
  * the launcher's env contract
    (/root/reference/python/paddle/distributed/launch.py:132,243)
  * dygraph's prepare_context / Env
    (/root/reference/python/paddle/fluid/dygraph/parallel.py:37)

Instead of broadcasting an ncclUniqueId over raw sockets, every process joins
the PjRt coordination service (`jax.distributed.initialize`). After that, XLA
sees ONE global device topology spanning all hosts; `jax.sharding.Mesh` built
over `jax.devices()` covers the pod, and collectives ride ICI within a host
slice and DCN across hosts — no per-link communicator objects exist anywhere.

CPU backend note (tests / TestDistBase pattern): cross-process CPU collectives
need the gloo implementation (`jax_cpu_collectives_implementation=gloo`);
`backend="cpu"` pins `jax_platforms` via jax.config, which also reaches a jax
that was imported before the launcher's env was read.
"""
from __future__ import annotations

import os

__all__ = ["ParallelEnv", "init_parallel_env"]

_initialized = False


class ParallelEnv:
    """Rank/world-size view of the launcher's env contract (reference
    dygraph/parallel.py Env: nranks/local_rank/dev_id/endpoints)."""

    def __init__(self):
        self.nranks = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        self.local_rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        self.current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        self.trainer_endpoints = eps.split(",") if eps else []

    @property
    def rank(self):
        return self.local_rank

    @property
    def world_size(self):
        return self.nranks


def init_parallel_env(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    backend: str | None = None,
    local_device_count: int | None = None,
) -> ParallelEnv:
    """Join the job's coordination service and initialize the global topology.

    Reads the `python -m paddle_tpu.distributed.launch` env contract when
    arguments are omitted. Must run before any JAX computation so the backend
    initializes with the distributed client (the PjRt analogue of "call
    prepare_context before the first forward", reference parallel.py:51).
    """
    global _initialized
    env = os.environ
    coordinator = coordinator or env.get("PADDLE_COORDINATOR", "")
    if num_processes is None:
        num_processes = int(env.get("PADDLE_TRAINERS_NUM", "1"))
    if process_id is None:
        process_id = int(env.get("PADDLE_TRAINER_ID", "0"))
    backend = backend or env.get("PADDLE_DIST_BACKEND") or None
    if local_device_count is None and env.get("PADDLE_LOCAL_DEVICES"):
        local_device_count = int(env["PADDLE_LOCAL_DEVICES"])

    if local_device_count:
        # must land in XLA_FLAGS before the first backend initialization
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={local_device_count}"
        )

    import jax

    if backend:
        jax.config.update("jax_platforms", backend)
        if backend == "cpu" and num_processes > 1:
            # cross-process CPU collectives ride gloo; a single process has
            # none to run and stays on the default implementation
            jax.config.update("jax_cpu_collectives_implementation", "gloo")

    if num_processes > 1 and not _initialized:
        if not coordinator:
            raise ValueError(
                "init_parallel_env: no coordinator address — pass one or run "
                "under `python -m paddle_tpu.distributed.launch`"
            )
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
    return ParallelEnv()
