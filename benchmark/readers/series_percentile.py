"""Nearest-rank percentile `q` of a series of readings the runner kept
(one per request, token or submission), times `scale`."""
from benchmark.harness import percentile


def read(result, series: str, q: float, scale: float = 1.0):
    values = result.series.get(series)
    return percentile(values, q) * scale if values else None
