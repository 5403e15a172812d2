"""How unevenly a labelled counter of the program's registry is spread over
its label values: the largest series over the mean of ALL of them. The
registry holds no series for a value that never counted, so the size of the
population comes from the configuration (`population`, a dotted key): 16
experts of which one took every token read 16.0, a perfect balance 1.0."""
import functools


def read(result, counter: str, population: str):
    series = [v for k, v in result.counters.items()
              if k.startswith(counter + "{")]
    if not series:
        return None
    n = functools.reduce(lambda t, k: t[k], population.split("."),
                         result.ctx.config)
    return max(series) / (sum(series) / n)
