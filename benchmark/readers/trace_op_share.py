"""Self time of the device operations whose name matches `pattern`, as a
share of the traced slice, mean over the cell's chips, in percent. On the
serial op line a collective's self time is time no compute hid, so the same
reader gives the exposed collective share."""
import re


def read(result, pattern: str):
    if not result.trace:
        return None
    rx = re.compile(pattern)
    matched = sum(s for name, s in result.trace["op_self_s"].items()
                  if rx.search(name))
    window_s = result.trace["window_s"]
    return matched / window_s * 100.0 if window_s else None
