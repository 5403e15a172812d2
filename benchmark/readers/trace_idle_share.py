"""Share of the traced slice in which no operation ran on the device: 1
minus the union of the device-op intervals over the slice, mean over the
cell's chips, in percent."""


def read(result):
    if not result.trace:
        return None
    return result.trace["idle_share"] * 100.0
