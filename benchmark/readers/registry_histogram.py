"""One statistic of a histogram series of the program's registry, as it
stood at the window's end (the runner reset it at the window's start).
`mean` is exact (sum over count); the quantiles are the registry's own
log-bucket estimates (8 buckets a decade, so within about 15%)."""


def read(result, series: str, stat: str, scale: float = 1.0):
    h = result.histograms.get(series)
    if not h or not h["count"]:
        return None
    value = h["sum"] / h["count"] if stat == "mean" else h[stat]
    return None if value is None else value * scale
