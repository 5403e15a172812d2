"""Seeded open-loop request traces for a served decoder.

One general generator: a mix is a dict of parameters (a cell file's
`traffic` block), never code. Extended from tools/_serve_ab.synth_workload
and synth_shared_prefix_workload (uniform lengths, one fixed max_new) with
heavy-tailed lengths, per-request output lengths and a total-length cap.

    {"arrivals": {"process": "poisson", "rate_per_s": 8.0},
     "shared":   {"count": 8, "tokens": 256, "zipf_a": 1.2},      (optional)
     "prompt":   {"dist": "lognormal", "median": 96, "sigma": 0.7,
                  "min": 32, "max": 384},
     "output":   {"dist": "lognormal", "median": 48, "sigma": 0.6,
                  "min": 16, "max": 128},
     "max_total": 496}

`prompt` is the part of the prompt no other request shares; with `shared`
each request is one of `count` fixed system prompts (zipf-ranked) followed
by that unique part. Arrival times, lengths and token ids come from three
independent streams, so the i-th request has the same lengths at every rate
and a warm-up replay can redraw the unique tokens (`redraw_unique`) without
touching lengths or shared prompts.

Token ids always come from `--seed`. The SCHEDULE (arrival times, lengths,
which shared prompt) comes from the mix's `schedule_seed` when it has one,
else from `--seed` too. A cell whose tails are judged pins it: the p90 of
some 200 first-token times over independent Poisson draws spreads by about
12% from sampling alone, wider than any bound the check allows, so such a
cell replays one drawn schedule — as one would replay a recorded trace —
and the runs differ in tokens and weights, not in the offered work.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_ARRIVALS, _LENGTHS, _TOKENS, _REDRAW = range(4)


@dataclass
class Request:
    index: int
    due_s: float            # offset from the window's start
    prompt: list            # token ids, shared prefix included
    max_new: int
    shared_id: int          # which shared prompt, -1 for none
    shared_len: int


def _draw_len(rng, spec: dict) -> int:
    dist = spec["dist"]
    lo, hi = int(spec["min"]), int(spec["max"])
    if dist == "uniform":
        return int(rng.integers(lo, hi + 1))
    if dist == "lognormal":
        v = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal())
        return int(min(hi, max(lo, round(v))))
    raise ValueError(f"unknown length distribution {dist!r}")


def _arrival_gaps(rng, spec: dict, n: int) -> np.ndarray:
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return rng.exponential(1.0 / float(spec["rate_per_s"]), n)


def generate(mix: dict, seed: int, seconds: float, vocab_size: int) -> list:
    """Every request due inside [0, seconds), in order of due time."""
    rate = float(mix["arrivals"]["rate_per_s"])
    schedule = int(mix.get("schedule_seed", seed))
    n_max = int(seconds * rate * 1.5 + 64)
    gaps = _arrival_gaps(np.random.default_rng([schedule, _ARRIVALS]),
                         mix["arrivals"], n_max)
    due = np.cumsum(gaps)
    due = due[due < seconds]
    if len(due) == n_max:
        raise ValueError("arrival draw too short for the window")
    len_rng = np.random.default_rng([schedule, _LENGTHS])
    tok_rng = np.random.default_rng([seed, _TOKENS])
    shared = mix.get("shared")
    shared_prompts, probs = [], None
    if shared:
        shared_prompts = [
            tok_rng.integers(1, vocab_size, int(shared["tokens"])).tolist()
            for _ in range(int(shared["count"]))]
        ranks = np.arange(1, len(shared_prompts) + 1,
                          dtype=np.float64) ** -float(shared["zipf_a"])
        probs = ranks / ranks.sum()
    out = []
    for i, t in enumerate(due):
        sid = int(len_rng.choice(len(shared_prompts), p=probs)) \
            if shared else -1
        head = shared_prompts[sid] if shared else []
        room = int(mix["max_total"]) - len(head)
        n_unique = min(_draw_len(len_rng, mix["prompt"]), room - 1)
        n_out = min(_draw_len(len_rng, mix["output"]), room - n_unique)
        unique = tok_rng.integers(1, vocab_size, n_unique).tolist()
        out.append(Request(i, float(t), head + unique, n_out, sid,
                           len(head)))
    return out


def redraw_unique(requests: list, seed: int, vocab_size: int) -> list:
    """The same requests with fresh unique tokens: same lengths, same shared
    prompts. A warm-up replays these, so that it fills the prefix cache with
    what a long-running server would hold (the shared prompts) and with
    nothing the measured trace will look up."""
    rng = np.random.default_rng([seed, _REDRAW])
    out = []
    for r in requests:
        n_unique = len(r.prompt) - r.shared_len
        unique = rng.integers(1, vocab_size, n_unique).tolist()
        out.append(Request(r.index, r.due_s, r.prompt[:r.shared_len] + unique,
                           r.max_new, r.shared_id, r.shared_len))
    return out
