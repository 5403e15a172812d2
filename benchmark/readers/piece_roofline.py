"""A declared PIECE's share of the chip's memory bandwidth or of its matrix
unit's peak, in percent, whichever bound is the tighter: what `kernel_roofline`
reads for one named kernel, for a piece that lowers to many operations (the
indexer's gather and scores, the gather and attention over latent rows),
whose device time is booked under its path by `profiler.device_time`.

    work a call    = counter `work` / counter `calls`, over the whole window
                     (x the configuration's `bytes_per_work` against `peak`,
                     x `ops_per_work` against `ops_peak`; dotted keys)
    seconds a call = self seconds, inside the traced slice, of the operations
                     whose path matches `paths`, over the calls the slice
                     held: the calls of the modules matching `modules` (the
                     decode program) x the layers a call runs (`per_call`, a
                     dotted key of the configuration)

Both sides are per call, as in `kernel_roofline`, so the slice and the
counters need not cover the same steps. The larger of the two shares is
reported: the bound that leaves the piece the least room; the file's
`bound` note says which that is on the chip. Nothing to read (no trace, a
program without `profiler.device_time`, no such counter, no such path: the
parent of the PR that adds the piece) returns None.
"""
import functools
import re

from benchmark.readers.trace_device_time_share import report


def _dotted(tree: dict, key: str):
    return functools.reduce(lambda t, k: t[k], key.split("."), tree)


def read(result, paths: str, modules: str, per_call: str, work: str,
         calls: str, peak: str, bytes_per_work: str,
         ops_per_work: str | None = None, ops_peak: str | None = None):
    ctx = result.ctx
    n_work, n_calls = result.counters.get(work), result.counters.get(calls)
    if not result.trace or ctx.peaks is None or not n_work or not n_calls:
        return None
    found = report(ctx.trace_dir)
    if found is None:
        return None
    rx, mx = re.compile(paths), re.compile(modules)
    seconds = sum(row["self_s"] for key, row in found["paths"].items()
                  if rx.search(key))
    steps = sum(row["calls"] for key, row in found["modules"].items()
                if mx.search(key))
    if not seconds or not steps:
        return None
    call_s = seconds / (steps * _dotted(ctx.config, per_call))
    per = n_work / n_calls
    shares = [per * _dotted(ctx.config, bytes_per_work) / call_s
              / ctx.peaks[peak]]
    if ops_per_work:
        shares.append(per * _dotted(ctx.config, ops_per_work) / call_s
                      / ctx.peaks[ops_peak])
    return max(shares) * 100.0
