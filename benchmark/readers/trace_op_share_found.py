"""`trace_op_share` for pieces the chip's compiler names by kind and shape
(an XLA fusion has no name of its own; only a `pallas_call` carries one):
self time of the device operations that match any of `patterns`, as a share
of the traced slice in percent, but only if EVERY pattern finds an
operation. A change of the program that renames one piece (another row
bucket, table width or fusion) then leaves the metric out of the line,
which a traced run of a listed cell is refused for, instead of reading a
smaller share under `better: lower`."""
import re


def read(result, patterns: list):
    if not result.trace:
        return None
    ops, window_s = result.trace["op_self_s"], result.trace["window_s"]
    found = [[s for name, s in ops.items() if re.search(p, name)]
             for p in patterns]
    if not window_s or not all(found):
        return None
    return sum(map(sum, found)) / window_s * 100.0
