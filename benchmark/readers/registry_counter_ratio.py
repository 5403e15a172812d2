"""A ratio of sums of registry counters over the window:
sum(numerator) / sum(denominator) * scale."""


def read(result, numerator: list, denominator: list, scale: float = 1.0):
    num = sum(result.counters.get(n, 0) for n in numerator)
    den = sum(result.counters.get(n, 0) for n in denominator)
    return num / den * scale if den else None
