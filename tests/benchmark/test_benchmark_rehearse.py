"""`benchmark/run.py --rehearse`: the whole path of a cell at a tiny size on
the CPU — set-up, warm-up, window, correctness against the plain reference,
the last line — plus the open-loop driver's clock on a stubbed engine. A CPU
run proves control flow, counts and agreement with the reference; it gives
no speed."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.runners import serve_open_loop as sol  # noqa: E402
from benchmark.traffic import open_loop  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
TRAIN_1, TRAIN_4 = "bert_base.s128", "bert_base.s128.dp4"
SERVE, CHAT = "bert_base_decoder.sessions.sat", "bert_base_decoder.chat.r80"


def _rehearse(capsys, cell, trace):
    rc = bench_run.main(["--workload", cell, "--seed", "3", "--seconds", "1",
                         "--trace", str(trace), "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(ln[len("notes "):]) for ln in lines
                 if ln.startswith("notes "))
    return rc, json.loads(lines[-1]), notes


def _reported(kind, cell):
    return {m["name"] for m in MANIFEST[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", [TRAIN_1, TRAIN_4, SERVE, CHAT])
def test_rehearsal_ends_in_the_contract_line(capsys, cell):
    rc, line, notes = _rehearse(capsys, cell, trace=0)
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    # every number `correct` rests on, beside its limit, last in the line
    assert list(line)[-1] == "compared" and len(line["compared"]) >= 4
    for name, c in line["compared"].items():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"], name
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # --trace 0 reports exactly the cell's end-to-end metrics, none zero
    assert set(line["metrics"]) == _reported("end_to_end", cell)
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert notes["window_compiles"] == 0


def test_training_rehearsal_agrees_with_the_reference(capsys):
    """bf16-AMP trainer against the float32 reference's three Adam steps,
    tiny sizes: losses within bf16 rounding, and the parameters moved the
    same way by the same length."""
    _, line, notes = _rehearse(capsys, TRAIN_1, trace=1)
    assert notes["loss_gap_worst"] < 0.02
    assert notes["update_cosine"] > 0.99
    assert abs(notes["update_rms_ratio"] - 1.0) < 0.005
    assert notes["loss_last10"] < notes["loss_first10"] + 0.1
    # --trace 1 reports per-layer metrics only; on the CPU no device plane
    # exists, so the trace readers return nothing and are left out
    assert set(line["metrics"]) <= _reported("per_layer", TRAIN_1)
    assert {"window_compiles", "host_dispatch_ms"} <= set(line["metrics"])
    assert "breakdown" not in line and "busy_s" not in line["device"]


def _all_rows_weighted(batches, lr):
    import numpy as np
    return [dict(b, lm_weight=np.ones_like(b["lm_weight"]))
            for b in batches], lr


# (cell, what the trainer would have done wrong — played by the reference,
# the comparison is symmetric —, the number that has to catch it)
FAULTS = [
    (TRAIN_1, "lr_x1.1", lambda b, lr: (b, lr * 1.1), "update_rms_ratio"),
    (TRAIN_1, "dropped_step", lambda b, lr: (b[:2], lr), "update_rms_ratio"),
    (TRAIN_1, "mask_ignored", _all_rows_weighted, "update_cosine"),
    (TRAIN_1, "batches_out_of_order",
     lambda b, lr: ([b[1], b[2], b[0]], lr), "update_cosine"),
    # data parallel over four: the gradient of one replica's rows alone
    (TRAIN_4, "one_replicas_gradient",
     lambda b, lr: ([{k: v[:len(v) // 4] for k, v in x.items()}
                     for x in b], lr), "update_cosine"),
]


@pytest.mark.parametrize("cell,fault,twist,caught_by", FAULTS,
                         ids=[f[1] for f in FAULTS])
def test_training_check_catches_a_wrong_update(capsys, monkeypatch, cell,
                                               fault, twist, caught_by):
    """`correct` must be more than 'finite': an optimizer that takes a
    wrong step, or a gradient of the wrong rows, fails it."""
    from benchmark.reference import encoder_mlm

    true_steps = encoder_mlm.first_steps

    def twisted(params, batches, cfg, lr, block_rows):
        batches, lr = twist(batches, lr)
        return true_steps(params, batches, cfg, lr=lr,
                          block_rows=min(block_rows, len(batches[0]["src_ids"])))

    monkeypatch.setattr(encoder_mlm, "first_steps", twisted)
    rc, line, notes = _rehearse(capsys, cell, trace=0)
    assert rc == 0 and line["correct"] is False
    tol = notes["tolerances"]
    if caught_by == "update_cosine":
        assert notes["update_cosine"] < tol["update_cosine_min"] - 0.1
    else:
        assert abs(notes["update_rms_ratio"] - 1.0) > \
            2 * tol["update_rms_tolerance"]


def test_serving_rehearsal_agrees_with_the_reference(capsys):
    """Prefill + paged decode (prefix cache on) against one teacher-forced
    forward of the plain reference, float32 on both sides."""
    _, line, notes = _rehearse(capsys, SERVE, trace=1)
    assert notes["sampled"] == sol.SAMPLE and notes["worst_gap"] < 1e-3
    assert notes["leaked_pages"] == 0 and notes["audit_problems"] == 0
    assert line["metrics"]["prefix_hit_rate.bert"]["value"] > 20
    assert line["metrics"]["batch_rows_mean.bert"]["value"] >= 1


class _SlowEngine:
    """An engine whose step() blocks for `step_s` and gives every queued
    request one token."""

    class _Live:
        def __init__(self, rid, max_new):
            self.rid, self.max_new = rid, max_new
            self.out_tokens, self.state = [], "waiting"

        @property
        def n_generated(self):
            return len(self.out_tokens)

    def __init__(self, step_s):
        self.step_s, self.requests = step_s, {}

    def submit(self, prompt, max_new):
        rid = len(self.requests)
        self.requests[rid] = self._Live(rid, max_new)
        return rid

    def has_work(self):
        return any(r.state != "finished" for r in self.requests.values())

    def step(self):
        time.sleep(self.step_s)
        for r in self.requests.values():
            if r.state != "finished":
                r.out_tokens.append(7)
                r.state = "finished" if r.n_generated >= r.max_new \
                    else "running"


def test_open_loop_times_from_the_due_time():
    """Two requests fall due while a 100 ms step blocks: they can only be
    submitted when it returns. A clock started at submit (the engine's
    arrival_t) would say their first token took one step; timed from when
    they were DUE it took nearly two; the wait for the blocked engine is
    reported, apart from the generator's own lateness."""
    step_s = 0.1
    reqs = [open_loop.Request(i, due, [1, 2, 3], 2, -1, 0)
            for i, due in enumerate((0.0, 0.01, 0.02))]
    tracks, active, _, steps, _ = sol.drive(
        _SlowEngine(step_s), reqs, seconds=0.5)
    assert not active and all(tr.state == "finished" for tr in tracks)
    assert [n for _, _, n in steps] == [1, 3, 2]
    s = sol.summarize(tracks, steps, seconds=0.5, settle_s=1.0)
    assert max(s["loop_iter_s"]) < step_s + 0.05
    wait = s["submit_wait_s"]
    assert wait[0] < 0.02 and wait[1] > 0.07 and wait[2] > 0.06
    # ...and none of that wait was the generator's own doing
    assert max(s["gen_late_s"]) < 0.02
    # first token: request 0 after one step, requests 1 and 2 after two,
    # counted from their due times
    assert step_s <= s["ttft_s"][0] < step_s + 0.05
    assert s["ttft_s"][1] > 2 * step_s - 0.02
    assert s["ttft_s"][2] > 2 * step_s - 0.03
    # two tokens a request, one gap each, one step long
    assert len(s["itl_s"]) == 3 and all(g >= step_s for g in s["itl_s"])
    assert s["tokens"] == 6


def test_a_stalled_iteration_stays_in_every_number():
    """The first and the sixth step take 0.4 s instead of 0.02. The clock is
    the real one: the stalls are in the tokens per second, in the gap the
    sixth stretched and in the first-token time of the request that fell
    due inside the first; the longest loop iteration is kept for the
    per-layer metric."""
    class Hiccup(_SlowEngine):
        steps = 0

        def step(self):
            self.steps += 1
            if self.steps in (1, 6):
                time.sleep(0.4)
            super().step()

    reqs = [open_loop.Request(0, 0.0, [1, 2, 3], 10, -1, 0),
            open_loop.Request(1, 0.1, [1, 2, 3], 1, -1, 0)]
    tracks, _, _, steps, _ = sol.drive(Hiccup(0.02), reqs, seconds=1.5)
    s = sol.summarize(tracks, steps, seconds=1.5, settle_s=1.0)
    assert 0.4 < max(s["loop_iter_s"]) < 0.6
    assert s["tokens"] == 11
    assert s["serve_tok_s"] == pytest.approx(11 / 1.5)
    assert len(s["itl_s"]) == 9 and 0.4 < max(s["itl_s"]) < 0.6
    # due at 0.1 s, inside the stalled first step: submitted only when it
    # returns, and timed from when it was due
    assert s["submit_wait_s"][1] > 0.3 and 0.3 < s["ttft_s"][1] < 0.6


def test_a_request_without_a_first_token_counts_as_the_longest_wait():
    class Deaf(_SlowEngine):
        def step(self):
            time.sleep(self.step_s)

    reqs = [open_loop.Request(0, 0.0, [1, 2, 3], 2, -1, 0)]
    tracks, _, _, steps, _ = sol.drive(Deaf(0.02), reqs, seconds=0.2)
    s = sol.summarize(tracks, steps, seconds=0.2, settle_s=0.3)
    assert s["ttft_s"] == [pytest.approx(0.5)] and s["tokens"] == 0
    assert s["serve_tok_s"] == 0.0 and s["sat_tok_s"] == 0.0


def test_a_steps_tokens_are_emitted_evenly_over_its_iteration():
    """The token curve both rates are read from: a step's tokens are stamped
    together when it returns, and counted as emitted evenly over the loop
    iteration that ran it, so a rate up to a fixed instant does not jump by
    a whole batch with the phase of the last step."""
    steps = [(0.0, 1.0, 10), (1.0, 2.0, 20), (2.5, 2.5, 4)]
    assert sol.emitted_by(steps, [0.0, 0.5, 1.0, 1.5, 2.0, 2.4, 3.0]) == \
        pytest.approx([0, 5, 10, 20, 30, 30, 34])
    assert list(sol.emitted_by([], [1.0, 2.0])) == [0.0, 0.0]
    # a step that straddles the window's end counts by its share inside
    s = sol.summarize([], [(0.0, 1.0, 10), (1.0, 3.0, 20)], 2.0, 1.0)
    assert s["serve_tok_s"] == pytest.approx(10.0)
    assert s["tok_s_by_second"] == [10.0, 10.0]
    # ...and so does its length: no iteration is longer than the other here
    assert s["sat_tok_s"] == pytest.approx(10.0)


@pytest.mark.parametrize("stalls, sat", [(0, 10.0), (1, 10.0), (2, 5.0)])
def test_the_saturated_rate_forgives_one_stall_and_no_second(stalls, sat):
    """A saturated engine emits 10 tokens in every one-second iteration of
    a 12 s window; `stalls` of its iterations take 3 s more. The mean rate
    loses every stall (above the knee nothing catches up). The saturated
    rate shortens the window by the excess of the ONE longest iteration
    over the second longest: a single stall is not in it; of two, the
    second longest is as long, and both are in it in full."""
    steps, t = [], 0.0
    while t < 12.0:
        d = 4.0 if len(steps) in (2, 5)[:stalls] else 1.0
        steps.append((t, t + d, 10))
        t += d
    s = sol.summarize([], steps, 12.0, 1.0)
    assert s["serve_tok_s"] == pytest.approx(10.0 * (12 - 3 * stalls) / 12)
    assert s["sat_tok_s"] == pytest.approx(sat)


def test_a_slower_step_is_in_the_saturated_rate_in_full():
    """Every iteration 10% longer: nothing is forgiven."""
    steps = [(1.1 * i, 1.1 * (i + 1), 10) for i in range(10)]
    s = sol.summarize([], steps, 11.0, 1.0)
    assert s["sat_tok_s"] == pytest.approx(10 / 1.1)
    assert s["serve_tok_s"] == pytest.approx(10 / 1.1)


def test_one_stall_on_the_clock_leaves_the_saturated_rate():
    """The same through `drive`: a token every 10 ms and one 0.35 s stall in
    a one-second window. The stall is the longest iteration, it is in the
    mean rate, and the saturated rate reads what the other steps gave."""
    class Stall(_SlowEngine):
        steps = 0

        def step(self):
            self.steps += 1
            if self.steps == 20:
                time.sleep(0.35)
            super().step()

    reqs = [open_loop.Request(0, 0.0, [1, 2, 3], 1000, -1, 0)]
    tracks, _, _, steps, _ = sol.drive(Stall(0.01), reqs, seconds=1.0)
    s = sol.summarize(tracks, steps, seconds=1.0, settle_s=1.0)
    assert 0.35 < max(s["loop_iter_s"]) < 0.5
    assert s["serve_tok_s"] < 0.75 * s["sat_tok_s"]
    assert 60 < s["sat_tok_s"] <= 100


def test_trace_is_seeded_and_rate_leaves_lengths_alone():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 50.0},
           "shared": {"count": 3, "tokens": 8, "zipf_a": 1.2},
           "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.7,
                      "min": 4, "max": 40},
           "output": {"dist": "uniform", "min": 2, "max": 9},
           "max_total": 48}
    a = open_loop.generate(mix, 5, 2.0, 97)
    b = open_loop.generate(mix, 5, 2.0, 97)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in b]
    assert a != open_loop.generate(mix, 6, 2.0, 97)
    fast = dict(mix, arrivals={"process": "poisson", "rate_per_s": 100.0})
    c = open_loop.generate(fast, 5, 2.0, 97)
    n = min(len(a), len(c))
    assert n > 20 and [r.prompt for r in a[:n]] == [r.prompt for r in c[:n]]
    for r in a:
        assert len(r.prompt) + r.max_new <= mix["max_total"]
        assert 8 + 4 <= len(r.prompt) <= 8 + 40 and r.shared_len == 8
    # a pinned schedule: another seed changes the tokens and nothing else
    pinned = dict(mix, schedule_seed=9)
    x, y = (open_loop.generate(pinned, sd, 2.0, 97) for sd in (5, 6))
    assert [(r.due_s, len(r.prompt), r.max_new, r.shared_id) for r in x] == \
        [(r.due_s, len(r.prompt), r.max_new, r.shared_id) for r in y]
    assert [r.prompt for r in x] != [r.prompt for r in y]
    # the warm-up's replay: same lengths and shared prompts, other tokens
    w = open_loop.redraw_unique(a, 5, 97)
    assert all(x.prompt[:8] == y.prompt[:8] and len(x.prompt) == len(y.prompt)
               for x, y in zip(a, w))
    assert any(x.prompt[8:] != y.prompt[8:] for x, y in zip(a, w))


def test_percentiles_are_nearest_rank_and_the_band_is_their_mean():
    from benchmark.harness import percentile, percentile_band

    v = [float(x) for x in range(1, 101)]            # 1..100
    assert percentile(v, 90) == 90.0 and percentile(v, 99) == 99.0
    assert percentile([5.0], 99) == 5.0
    assert percentile_band(v, 85, 95) == pytest.approx(90.5)   # 86..95
    assert percentile_band([3.0, 9.0], 85, 95) == 9.0


def test_off_chip_the_command_refuses(capsys):
    """Without --rehearse a CPU is not a device to measure on."""
    rc = bench_run.main(["--workload", TRAIN_1, "--seed", "1", "--seconds",
                         "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "needs a TPU" in out.err
