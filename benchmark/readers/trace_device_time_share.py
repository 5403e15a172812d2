"""Self time of the device operations the PROGRAM names, as a share of the
traced slice (`"of": "window"`) or of the device's busy seconds (`"of":
"busy"`), mean over the cell's chips, in percent.

`paths` is a regular expression searched in an operation's path: the name
scope it was appended under, its framework op type and, in a served stack,
the mode and the piece that lowered it (`sparse_moe_stack/decode/indexer`,
`mlm_head/matmul_grad`; an instruction no framework op names is
`unscoped/<kind and shape>`). `modules` is one searched in the name of the
compiled module an operation ran in, `jit_<Program.name>(<program id>)`.
The names are declared in `paddle_tpu/observability/schema.py`, reach the
trace through the compiled modules' metadata and are read back by
`paddle_tpu.profiler.device_time` (once a process: the reduction is
memoised by trace directory); tests/benchmark/test_benchmark_device_names.py
holds every pattern of a `layer_metrics` file to what the cells' rehearsal
programs compile.

The names are those of the tree that COMPILED an executable: jax leaves
`op_name` out of the persistent compile cache's key, so after a change
that only moves a piece's boundary or renames a scope, a machine whose
cache is warm (`JAX_COMPILATION_CACHE_DIR`, or `<checkout>/.jax_cache`)
reads the old names until that directory is emptied. A change to what is
computed compiles anew and needs nothing.

Nothing to read returns None and never 0: no trace, a program without
`profiler.device_time` (the parent of the PR that adds it), a pattern that
matches nothing that ran.
"""
import functools
import re

WINDOW_SPAN = "bench.trace_slice"


@functools.lru_cache(maxsize=2)
def report(trace_dir: str):
    from paddle_tpu import profiler

    device_time = getattr(profiler, "device_time", None)
    return device_time(trace_dir, window_span=WINDOW_SPAN) \
        if device_time else None


def read(result, of: str, paths: str | None = None,
         modules: str | None = None):
    if not result.trace:
        return None
    found = report(result.ctx.trace_dir)
    if found is None:
        return None
    rows, pattern = (found["paths"], paths) if modules is None \
        else (found["modules"], modules)
    rx = re.compile(pattern)
    matched = [row["self_s"] for key, row in rows.items() if rx.search(key)]
    whole = found["window_s"] if of == "window" else found["busy_s"]
    if not matched or not whole:
        return None
    return sum(matched) / whole * 100.0
