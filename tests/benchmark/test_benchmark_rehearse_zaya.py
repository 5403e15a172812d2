"""`benchmark/run.py --rehearse` for the cell PR 25 added: the ZAYA1 cell's
whole path on the CPU at a tiny size (routed runner, routed reference, the
contract line) and what its traced line can carry without a device."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
ZAYA = "zaya1_8b.decode.sat"


def _rehearse(capsys, cell, trace):
    rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", "1", "--trace", str(trace),
                         "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(ln[len("notes "):]) for ln in lines
                 if ln.startswith("notes "))
    return rc, json.loads(lines[-1]), notes


def test_rehearsal_ends_in_the_contract_line(capsys):
    cell = ZAYA
    rc, line, notes = _rehearse(capsys, cell, trace=0)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert notes["window_compiles"] == 0


def test_zaya_rehearsal_follows_the_engines_routes(capsys):
    rc, line, notes = _rehearse(capsys, ZAYA, trace=1)
    assert rc == 0 and line["correct"] is True
    assert notes["sampled"] > 0
    assert notes["worst_gap"] <= notes["tolerance"]
    assert notes["worst_route_margin"] <= notes["route_margin_tolerance"]
    # the counters behind the new per-layer metrics read on the CPU; the
    # trace-fed ones (shares, rooflines) find no device operation and are
    # left out of the line, as on a parent without the kernels
    got = line["metrics"]
    assert 1.0 <= got["experts_touched_mean"]["value"] <= 4.0
    assert 1.0 <= got["expert_load_max_over_mean"]["value"] <= 4.0
    assert got["prefix_hit_rate"]["value"] > 0
    assert got["batch_rows_mean"]["value"] >= 1.0
    assert got["window_compiles"]["value"] == 0
    assert not {"moe_experts_share", "moe_experts_roofline",
                "paged_decode_gqa_roofline"} & set(got)


def test_a_wrong_route_or_logit_fails_the_check():
    """check_sample marks a request wrong on either limit."""
    import types

    from benchmark.runners import serve_open_loop_routed as routed

    class Reference:
        graded = []

        @staticmethod
        def read_params(get, cfg):
            return {}

        @classmethod
        def check_sequences(cls, params, sequences, cfg):
            return cls.graded[:len(sequences)]

    sys.modules["_routed_fake_reference"] = Reference
    live = types.SimpleNamespace(routes=[[0]])
    request = types.SimpleNamespace(prompt=[1, 2])
    tracks = [types.SimpleNamespace(state="finished", served=[3], live=live,
                                    request=request) for _ in range(3)]
    ctx = types.SimpleNamespace(seed=1, config={"reference": {
        "module": "_routed_fake_reference", "logit_tolerance": 0.1,
        "route_margin_tolerance": 0.2}})
    engine = types.SimpleNamespace(_scope=types.SimpleNamespace(
        find_var=None))
    Reference.graded = [{"gap": 0.05, "route_margin": 0.1},
                        {"gap": 0.5, "route_margin": 0.0},
                        {"gap": 0.0, "route_margin": 0.3}]
    out = routed.check_sample(engine, None, tracks, ctx)
    assert out["sampled"] == 3 and len(out["wrong"]) == 2
    assert out["worst_gap"] == 0.5 and out["worst_route_margin"] == 0.3


def test_a_row_the_clock_cut_is_not_judged_and_a_stalled_one_fails():
    """`judged` leaves out what `settle` cut while it was served, keeps a
    row that had stopped getting tokens, and keeps every other state."""
    import types

    from benchmark.runners import serve_open_loop_routed as routed

    def track(state, token_s, due_s=0.0):
        return types.SimpleNamespace(
            state=state, token_s=token_s,
            request=types.SimpleNamespace(due_s=due_s))

    cut_s = 40.0
    done = track("finished", [1.0, 2.0])
    served_to_the_cut = track("unfinished", [29.0, 39.98])
    stalled = track("unfinished", [29.0, 35.0])
    ended_by_the_engine = track("deadline_exceeded", [5.0])
    admitted_late = track("unfinished", [30.5, 39.98])
    never_admitted = track("aborted", [])
    tracks = [done, served_to_the_cut, stalled, ended_by_the_engine,
              admitted_late, never_admitted]
    kept, cut = routed.judged(tracks, "admitted", 30.0, cut_s)
    assert kept == [done, stalled, ended_by_the_engine] and cut == 1
    assert sum(tr.state != "finished" for tr in kept) == 2
    # below the knee every request due early is judged, served or not
    kept, cut = routed.judged(tracks, "due", 30.0, cut_s)
    assert served_to_the_cut not in kept and admitted_late not in kept
    assert never_admitted in kept and stalled in kept and cut == 2
