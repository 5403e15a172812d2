"""Record the small device trace tests/benchmark/test_benchmark_trace_reduce.py
reads. Run on the chip (all the chips of the host), by hand, when the
recorded file has to be made again:

    chiprun --chips 4 -- python3 tests/benchmark/record_tiny_trace.py

A few steps of a tiny data-parallel program (two matmuls and the all-reduce
XLA puts between them), each under a `bench.step` span, with a short sleep
under `bench.idle` between steps. Writes chiprun_out/tiny_trace.xplane.pb
(well under 1 MB: no Python tracer, four steps).
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main() -> int:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_tiny_trace.py: needs a TPU", file=sys.stderr)
        return 2
    mesh = Mesh(np.array(devices), ("dp",))
    rows = NamedSharding(mesh, P("dp", None))
    whole = NamedSharding(mesh, P(None, None))

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w)
        return w - 1e-3 * (x.T @ y)     # contracts the sharded rows

    x = jax.device_put(np.ones((len(devices) * 512, 1024), np.float32), rows)
    w = jax.device_put(np.eye(1024, dtype=np.float32), whole)
    w = jax.block_until_ready(step(x, w))
    out = os.path.join("chiprun_out", "tiny_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.trace_slice"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.step"):
                w = jax.block_until_ready(step(x, w))
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.003)
    jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(pb, os.path.join("chiprun_out", "tiny_trace.xplane.pb"))
    shutil.rmtree(out)
    print(os.path.getsize(os.path.join("chiprun_out",
                                       "tiny_trace.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
