"""Seeded host batches for a training step, drawn from a config's `feeds`
list. Each entry names a program feed, its shape in symbols the cell's
traffic block defines (`$rows`, `$seq_len`, ...) and how it is drawn:

    randint      integers in [low, high)
    arange_last  0..n-1 along the last axis, repeated (position ids)
    bernoulli    1.0 with probability p, else 0.0 (loss weights)
    normal       standard normal (images)
"""
from __future__ import annotations

import numpy as np


def resolve(value, symbols: dict):
    """`"$name"` -> symbols[name]; anything else unchanged."""
    if isinstance(value, str) and value.startswith("$"):
        return symbols[value[1:]]
    return value


def draw_batch(feeds: list, symbols: dict, rng) -> dict:
    batch = {}
    for spec in feeds:
        shape = tuple(int(resolve(d, symbols)) for d in spec["shape"])
        draw = spec["draw"]
        if draw == "randint":
            arr = rng.integers(int(resolve(spec["low"], symbols)),
                               int(resolve(spec["high"], symbols)), shape)
        elif draw == "arange_last":
            arr = np.broadcast_to(np.arange(shape[-1]), shape)
        elif draw == "bernoulli":
            arr = rng.random(shape) < float(spec["p"])
        elif draw == "normal":
            arr = rng.standard_normal(shape)
        else:
            raise ValueError(f"feed {spec['name']!r}: unknown draw {draw!r}")
        batch[spec["name"]] = np.ascontiguousarray(arr, dtype=spec["dtype"])
    return batch


def batch_ring(feeds: list, symbols: dict, seed: int, n: int) -> list:
    """`n` distinct batches; the window feeds them round robin, so every
    step moves fresh host bytes through the feed path."""
    rng = np.random.default_rng([seed, 11])
    return [draw_batch(feeds, symbols, rng) for _ in range(n)]
