"""Does a compiled serving program move a whole KV pool?

The paged KV pool has ONE device layout (`serving/kv_cache.py`): every
serving program reads it and updates it in place. A compiler that disagrees
shows it in the optimized HLO as an instruction that produces a pool-sized
array that is not the in-place update itself: a `copy` between two layouts,
a `transpose`, a `bitcast-convert`. On the v5e such a copy of 24 pools took
86% of the device's time in every decode and prefill step (PERF.md, PR 24).

`pool_sized_copies(hlo_text, pool_elements)` finds them in an optimized HLO
text; `updates_out_of_place(hlo_text, pool_elements)` names the in-place
updates whose pool is read again after them (a rematerialised scatter ran
twice a step until PR 44: a lead, which `main` prints and does not fail
on); `token_row_gathers(hlo_text, row_elements)` lists the gathers that
fetch one token's row at a time (a block that gathers tokens pays by the
row: its decode program holds ONE a layer since PR 30);
`sorts_over(hlo_text, row_elements)` lists the sorts of rows at least that
long (a block that selects sorted every decode row's whole context until
PR 34: none since); `kernel_calls(hlo_text, name)` counts the calls of a
Pallas kernel;
`serving_program_hlos(engine)` compiles the engine's decode, prefill,
window and COW programs (`serving_program_cases`) at one signature each and
returns their texts.
`chip_smoke.py` fails on a finding; run here it prints the table:

    python tools/pool_hlo.py [--pool-pages 3072] [--page-size 16]
    python tools/pool_hlo.py --config zaya1_8b --layers 2 --dump <dir>

(`--config`: the engine of `benchmark/configs/<name>.json`, any block
family, `--layers` deep where the family is one scanned layer or a
pattern of one character a layer; every pool
the family keeps is listed, the sliding layers' second pool too.)

(on a host without a TPU it compiles for a described v5e: what the chip's
compiler would emit, nothing run).
"""
from __future__ import annotations

import math
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

__all__ = ["pool_sized_copies", "updates_out_of_place", "token_row_gathers",
           "sorts_over",
           "serving_program_cases", "serving_program_hlos"]

# `  %name = f32[3072,16,768]{2,1,0:T(8,128)} opcode(operands...), attrs`
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\](?P<layout>\{[^}]*\})?\s+"
    r"(?P<op>[\w\-]+)\((?P<rest>.*)$")
_OPERAND = re.compile(r"%(?P<name>[\w.\-]+)")

_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\{\s*$")
_ROOT = re.compile(r"^\s*ROOT\s+%?[\w.\-]+\s*=\s*\S+\s+(?P<op>[\w\-]+)\(")
_CALLS = re.compile(r"calls=%?(?P<name>[\w.\-]+)")

# Opcodes that hand a pool on without touching its bytes, or update it in
# place: everything else that yields a pool-sized array moved a pool.
_PASS_ON = frozenset({
    "parameter", "get-tuple-element", "tuple", "bitcast", "while",
    "conditional", "call", "optimization-barrier",
    "dynamic-update-slice", "scatter", "custom-call"})
# A fusion is in place when the computation it calls ends in one of these.
_UPDATES = frozenset({"dynamic-update-slice", "scatter"})


def _elements(dims: str) -> int:
    return math.prod(int(d) for d in dims.split(",") if d) if dims else 1


def _fused_roots(hlo_text: str) -> dict[str, str]:
    """{name of a computation some fusion calls: opcode of its ROOT}."""
    fused = {m["name"] for line in hlo_text.splitlines()
             if " fusion(" in line and (m := _CALLS.search(line))}
    roots, current = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m is not None:
            current = m["name"]
        elif current in fused and (r := _ROOT.match(line)) is not None:
            roots[current] = r["op"]
    return roots


def pool_sized_copies(hlo_text: str, pool_elements: int) -> list[dict]:
    """Instructions of an optimized HLO text whose result has
    `pool_elements` elements and that are not an in-place update (nor a
    parameter, tuple plumbing, a bitcast or a kernel call): `[{"name", "op",
    "shape", "layout", "from_layout", "line"}]`, in text order. A `copy`,
    `copy-done`, `transpose`, `convert`, or a fusion that ends in anything
    but a scatter or a dynamic-update-slice, wrote a fresh pool-sized
    buffer; `from_layout` is its first pool-sized operand's layout. What a
    fused computation holds inside is no buffer and is not looked at."""
    fused = _fused_roots(hlo_text)
    found = []
    pools: dict[str, str] = {}      # pool-sized value -> its layout
    current = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c is not None:
            current = c["name"]
            continue
        m = _INSTR.match(line)
        if (m is None or current in fused
                or _elements(m["dims"]) != pool_elements):
            continue
        op, layout = m["op"], m["layout"] or ""
        pools[m["name"]] = layout
        if op in _PASS_ON:
            continue
        if op == "fusion":
            called = _CALLS.search(line)
            if called and fused.get(called["name"]) in _UPDATES:
                continue
        # operands come before the first attribute (`), kind=`, `, calls=`)
        operands = m["rest"].split(")", 1)[0]
        src = next((pools[o["name"]] for o in _OPERAND.finditer(operands)
                    if o["name"] in pools and o["name"] != m["name"]), "")
        found.append({
            "name": m["name"], "op": op,
            "shape": f"{m['dtype']}[{m['dims']}]",
            "layout": layout, "from_layout": src,
            "line": line.strip()[:400],
        })
    return found


def updates_out_of_place(hlo_text: str, pool_elements: int) -> list[dict]:
    """The updates of an optimized HLO text (a `scatter`, a `dynamic-update-
    slice`, or a fusion that ends in one) that yield `pool_elements`
    elements while the pool they update, their first pool-sized operand,
    has another user LATER in the same computation: `[{"name", "op",
    "shape", "pool", "read_again_by"}]`, in text order, which is the
    schedule's in a module that says `is_scheduled=true`. The operand's
    buffer is still needed, so the output cannot simply take it.
    `pool_sized_copies` passes these, because it looks at the instruction
    and not at its operand's other users. A lead, not a verdict (`main`
    prints it and does not fail on it): it names the rematerialised
    scatter of PR 43's decode program, which the chip ran a sixth time a
    step, and also the rematerialised clones of a window's one-row
    `dynamic-update-slice`, which the chip ran in 4-5 us each, in place
    after all (PERF.md, PR 44)."""
    fused = _fused_roots(hlo_text)
    seen: list = []
    live: list = []                 # the current computation's updates
    pools: set = set()
    current = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c is not None:
            current, pools, live = c["name"], set(), []
            continue
        m = _INSTR.match(line)
        if m is None or current in fused:
            continue
        operands = [o["name"] for o in _OPERAND.finditer(
            m["rest"].split(")", 1)[0])]
        for update in live:
            if not update["read_again_by"] and update["pool"] in operands:
                update["read_again_by"] = m["name"]
        if _elements(m["dims"]) != pool_elements:
            continue
        pools.add(m["name"])
        called = _CALLS.search(line) if m["op"] == "fusion" else None
        pool = next((o for o in operands if o in pools and o != m["name"]),
                    None)
        if pool is not None and (m["op"] in _UPDATES or (
                called and fused.get(called["name"]) in _UPDATES)):
            live.append({"name": m["name"], "op": m["op"],
                         "shape": f"{m['dtype']}[{m['dims']}]",
                         "pool": pool, "read_again_by": ""})
            seen.append(live[-1])
    return [update for update in seen if update["read_again_by"]]


_GATHER = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>\S+)\s+gather\("
    r".*slice_sizes=\{(?P<slice>[\d,]*)\}")


def token_row_gathers(hlo_text: str, row_elements: int) -> list[dict]:
    """The gathers of an optimized HLO text (inside fusions too) whose
    slice is one row of `row_elements` values out of a two-dimensional
    operand, `[{"name", "shape"}]` in text order: a pool seen as `[tokens,
    row]` read a token at a time. A scanned layer's body appears once."""
    want = f"1,{int(row_elements)}"
    return [{"name": m["name"], "shape": m["shape"]}
            for line in hlo_text.splitlines()
            if (m := _GATHER.match(line)) and m["slice"] == want]


_SORT = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*\(?"
    r"(?P<shape>[a-z]+\d*\[(?P<dims>[\d,]*)\])\S*.*\ssort\("
    r".*dimensions=\{(?P<dim>\d+)\}")


def sorts_over(hlo_text: str, row_elements: int) -> list[dict]:
    """The sorts of an optimized HLO text whose sorted dimension holds at
    least `row_elements` values, `[{"name", "shape"}]` in text order (the
    first operand's shape: `lax.top_k` sorts keys and positions together).
    A router's top-k over its experts is far shorter than a context."""
    return [{"name": m["name"], "shape": m["shape"]}
            for line in hlo_text.splitlines()
            if (m := _SORT.match(line))
            and int(m["dims"].split(",")[int(m["dim"])]) >= row_elements]


def kernel_calls(hlo_text: str, name: str) -> int:
    """How many instructions of an optimized HLO text call the Pallas
    kernel `name` (the `name=` of its `pallas_call`: the instruction is
    called after it). A scanned layer's body appears once."""
    return len(re.findall(
        rf"^\s*(?:ROOT\s+)?%{re.escape(name)}[.\d]*\s*=\s*\S+\s+"
        r"custom-call\(", hlo_text, flags=re.M))


def _program_hlo(exe, target, feed, fetch_list, scope, device=None) -> str:
    """Optimized HLO of the executable `exe.run(target, feed, fetch_list,
    scope)` would dispatch, compiled for the arrays' own device or, with
    `device` (a described TPU), for that one from shapes alone."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.compiler import CompiledProgram
    from paddle_tpu.framework import Variable

    mesh, spmd_mode, program = None, "gspmd", target
    if isinstance(target, CompiledProgram):
        mesh, spmd_mode = target._mesh, target._spmd_mode
        program = target._program
    fetch_names = [v.name if isinstance(v, Variable) else str(v)
                   for v in fetch_list]
    comp, feed_vals, ro_vals, rw_vals, key, _, _ = exe._prepare_step(
        program, feed, fetch_names, scope, mesh, spmd_mode, None)
    args = (tuple(feed_vals), ro_vals, rw_vals, key)
    if device is None:
        return comp.fn.lower(*args).compile().as_text()
    sh = SingleDeviceSharding(device)
    args = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype, sharding=sh),
        args)
    # the dispatch asks which backend this process has; the program is
    # compiled for the described chip, so it takes the chip's branch
    from unittest import mock

    from paddle_tpu.ops.pallas_kernels import workbench

    with mock.patch.object(workbench, "on_tpu", lambda: True):
        return comp.fn.lower(*args).compile().as_text()


def serving_program_cases(engine, rows: int = 64, pages: int = 32,
                          prompt: int = 128) -> dict[str, tuple]:
    """One run of each of the serving programs of `engine` (four, and the
    state copy where the family has a recurrent state), as
    `{name: (target, feed, fetch_list)}` for `Executor.run`: decode at
    `rows` x `pages`, cold prefill of a `prompt` bucket, the window program
    (suffix prefill) of the same bucket behind `pages` pages, and
    copy-on-write of page 0 onto itself. Every row is masked or of length
    0, so a run writes nothing. Feeds and fetches are the engine's own
    (`_mark_feed`, `_slot_feed`, `_state_feed`, `_window_feed`: the compact
    tables of a second, sliding-window pool and the slots of a recurrent
    state where the family has them, `_step_fetches`): the
    signature it serves with."""
    from paddle_tpu.serving import model as m

    e, i32 = engine, np.int32
    return {
        "decode": (e._decode_run, {
            m.TOK_FEED: np.zeros((rows, 1), i32),
            m.POS_FEED: np.zeros((rows,), i32),
            m.PAGES_FEED: np.zeros((rows, pages), i32),
            m.MASK_FEED: np.zeros((rows, 1), np.float32),
            **e._mark_feed(), **e._slot_feed((), rows, decode=True),
            **e._state_feed((), rows),
            **e._window_feed((), rows, e._wtable_decode)},
            e._step_fetches(e._decode_io)),
        "prefill": (e._prefill_run, {
            m.TOK_FEED: np.zeros((1, prompt), i32),
            m.POS_FEED: np.zeros((1, prompt), i32),
            m.PAGES_FEED: np.zeros((1, e.pool.pages_for(prompt)), i32),
            m.LEN_FEED: np.zeros((1,), i32), **e._slot_feed((), 1),
            **e._state_feed((), 1),
            **e._window_feed((), 1, e._wtable_chunk)},
            e._step_fetches(e._prefill_io, "last_logits")),
        "window": (e._window_run, {
            m.TOK_FEED: np.zeros((1, prompt), i32),
            m.POS_FEED: np.zeros((1, prompt), i32),
            m.PAGES_FEED: np.zeros((1, pages), i32),
            m.START_FEED: np.zeros((1,), i32),
            m.LEN_FEED: np.zeros((1,), i32), **e._slot_feed((), 1),
            **e._state_feed((), 1),
            **e._window_feed((), 1, e._wtable_chunk)},
            e._step_fetches(e._window_io, "last_logits")),
        # a family with a second pool copies a page of each
        "cow": (e._cow_run, {name: np.zeros((1,), i32)
                             for name in e._cow_io["feeds"]}, []),
        # a family with a recurrent state: one slot of it onto another
        **({"state_copy": (e._state_copy_run, {
            m.SCOPY_SRC_FEED: np.zeros((1,), i32),
            m.SCOPY_DST_FEED: np.zeros((1,), i32)}, [])}
           if e._state_copy_run is not None else {}),
    }


def serving_program_hlos(engine, rows: int = 64, pages: int = 32,
                         prompt: int = 128, device=None) -> dict[str, str]:
    """Optimized HLO text of the four `serving_program_cases` of `engine`,
    compiled for its own device or for `device`, a described TPU."""
    return {name: _program_hlo(engine._exe, target, feed, fetches,
                               engine._scope, device)
            for name, (target, feed, fetches) in serving_program_cases(
                engine, rows, pages, prompt).items()}


def main(argv=None) -> int:
    import argparse
    import json

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from paddle_tpu.serving import DecoderConfig, ServingEngine

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pool-pages", type=int, default=3072)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--config", default=None,
                    help="a file of benchmark/configs (its engine block)")
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--pages", type=int, default=32)
    ap.add_argument("--dump", default=None,
                    help="directory to write <program>.hlo.txt into")
    a = ap.parse_args(argv)

    device = None
    if jax.devices()[0].platform != "tpu":
        from jax.experimental import topologies

        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    if a.config:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               a.config + ".json")) as f:
            spec = json.load(f)["engine"]
        kw = dict(spec["config_kwargs"])
        if a.layers and "layer_types" not in kw:
            kw["num_layers"] = a.layers
            if "layer_pattern" in kw:   # a plan of one character a layer
                kw["layer_pattern"] = kw["layer_pattern"][:a.layers]
        cfg = DecoderConfig(**kw)
        eng = ServingEngine(cfg, page_size=spec["page_size"],
                            pool_pages=spec["pool_pages"],
                            max_inflight=spec["max_inflight"])
    else:
        cfg = DecoderConfig(num_layers=a.layers or 12)
        eng = ServingEngine(cfg, page_size=a.page_size,
                            pool_pages=a.pool_pages, max_inflight=64)
    # every pool the family keeps, by its own size
    pools = {name: int(np.prod(np.shape(eng._scope.find_var(name))))
             for name in sorted(eng._scope.var_names())
             if name.startswith("kv_cache.")}
    print(json.dumps({"pools": pools}), flush=True)
    bad = 0
    texts = serving_program_hlos(eng, rows=a.rows, pages=a.pages,
                                 device=device)
    for name, text in texts.items():
        if a.dump:
            os.makedirs(a.dump, exist_ok=True)
            with open(os.path.join(a.dump, f"{name}.hlo.txt"), "w") as f:
                f.write(text)
        sizes = sorted(set(pools.values()))
        found = [c for n in sizes for c in pool_sized_copies(text, n)]
        blocked = [u for n in sizes for u in updates_out_of_place(text, n)]
        bad += len(found)
        kinds: dict = {}
        for c in found:
            k = (c["op"], c["shape"], c["from_layout"], c["layout"])
            kinds[k] = kinds.get(k, 0) + 1
        print(json.dumps({
            "program": name, "pool_sized_copies": len(found),
            "compiled_for": "described v5e" if device else "this chip",
            "kinds": [{"op": k[0], "shape": k[1], "from": k[2], "to": k[3],
                       "n": n} for k, n in kinds.items()],
            "updates_out_of_place": blocked}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
