"""Expert layer over stacked expert weights, top-1 or top-k: a Pallas TPU
kernel.

What it computes: `out[t] = sum_e cw[t, e] * W_down[e] (silu(W_gate[e] z_t)
* (W_up[e] z_t))` for the experts this call holds, with `cw[t, e]` the
weight the router gave expert e for token t (its probability under top-1
routing, its renormalised share under top-k) and zero for every expert the
token did not choose. One term of the sum is non-zero per token under
top-1 routing, k under top-k.

Two forms of one sum, chosen by the ROW COUNT the call can see:

**Up to one token tile (256 rows: every decode step)** the kernel walks
EVERY expert it holds, so a step's time does not depend on where the
router sent the tokens (a decode batch of 64 rows touches nearly all of 16
experts at top-1 and 126 of 128 at top-8 anyway, and a step whose cost
moved with the routing would make two runs with different weights
incomparable). A decode step is bound by streaming the expert weights
(3 x H x F a expert) through the chip once, and this is that stream and
nothing else:

  * the weights stay where they live, stacked `[layers, experts, ...]`; the
    layer index is a prefetched scalar the BlockSpec index maps read, so a
    loop over layers (`lax.scan`) slices nothing out of the stack;
  * grid (token tile, expert, F tile): one step DMAs a `[H, tf]` slab of
    W_gate and W_up and a `[tf, H]` slab of W_down, multiplies the token
    tile through all three on the MXU (operands in the weights' dtype,
    float32 accumulation) and adds into the output tile, which stays in
    VMEM across the (expert, F tile) steps of its token tile;
  * the combine weight is applied to the `[tt, tf]` hidden tile before the
    down projection, so a token's row is zero for every expert it did not
    choose.

**More rows than one token tile (a prefill window or chunk: PR 52)** run
the GROUPED form (`_grouped_call`). The walk would stream every expert once
a token tile and multiply every token by every expert: a 2,048-token window
at top-8 of 512 with 128 held read 8 x 1.51 GB and computed sixty times the
products it needed, 16.6 ms a layer. Instead:

  * `_sort_rows` (XLA: a `top_k`, one `sort` of tokens x k keys, one row
    gather) lists the live (token, expert) pairs sorted by expert, with no
    padding between the groups; `rows` = tokens x min(k, E) is the static
    bound, the dead pairs lie behind the live ones and no tile of them is
    ever fetched. Every pair with a non-zero weight is computed: no
    capacity, nothing dropped;
  * grid (visit, F tile): the sorted list is cut into tiles of 256 rows, and
    an expert VISITS every tile that holds one of its rows
    (`grouped_visits`). Expert and tile of a visit are prefetched scalars
    the index maps read, so consecutive visits of one expert keep its
    slabs (the F tiles are walked forwards and backwards in turn) and
    consecutive visits of one tile keep its rows: **an expert's weights
    cross HBM once a call, and not at all where no row chose it**. The
    body is the walk's (same operand dtypes, float32 accumulation, the
    combine weight on the hidden tile before the down projection);
  * a visit's products `[256, H]` are added, row by row, into the rows of
    their tokens in the float32 output `[tokens, H]`, which stays in VMEM
    for the whole call and is written once (a window of more tokens than
    fit, `_resident_tokens`, goes block by block). The same k terms a
    token as the walk sums, in float32, in expert order.

What a grouped call's time depends on: the experts its rows TOUCH (each
16 us at Ling's 11.8 MB an expert, whatever its rows up to 256: the matrix
unit's time for three slabs is the time to load them) plus the tiles two
groups share. On the chip (`tools/kernel_check.py`, PR 52; the walk timed
on the same arrays) 128 held of 512 x 2,560 -> 768 at top-8: 2,048 tokens
16.63 -> 2.83 ms, 1,024 8.36 -> 2.60, 512 4.22 -> 2.42, every expert
touched in all three. So a window's time now moves with the routing: in
`ling3_flash.agent8k.sat`'s traced slice the 512-token windows' calls read
1.34-1.50 ms (a few hundred real rows, and padding rows that all choose the
same eight experts, leave some experts without a row) and all windows' calls
1.20-2.18 ms, where the walk read 2.07 ms a token tile whatever the routing;
across two seeds the cell's `sat_tok_s` stood 0.2% apart (3,434.3 | 3,427.9).
Decode steps, which the cells' rooflines read
(`^moe_top[1k]_experts_decode`), do not move.

A second arm, UNGATED (`moe_relu2_experts`): `out[t] = sum_e cw[t, e] *
W_2[e] relu(W_1[e] u_t)^2`, two matrices an expert and no gate (experts
that work in a latent `u` narrower than the hidden size: `mixer_moe_ops`).
Same grid, same prefetched layer index, same combine weight on the hidden
tile before the second product; a grid step DMAs ONE `[Z, tf]` slab and one
`[tf, Z]` slab. It runs under a kernel name of its own
(`moe_relu2_experts_<tag>`), so that a trace tells the two streams apart and
the gated arm's programs are what they were.

Forward only: serving never differentiates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False

_LANES = 128
_TOKEN_TILE = 256
_VMEM_LIMIT = 64 * 1024 * 1024


# three `[H, tile]` slabs of one grid step, single-buffered: what a wide
# tile (512, 384, a whole width) may take, and what a narrow one may where
# the wide one does not fit (twice that is double-buffered beside the token
# tile and the float32 output tile, under `_VMEM_LIMIT`)
_WIDE_SLABS = 8 * 1024 * 1024
_NARROW_SLABS = 12 * 1024 * 1024


def _f_tile(ffn: int, hidden: int = 0, itemsize: int = 2) -> int:
    """Columns of an expert's width one grid step takes: 512, or 384 for a
    width only that divides (768 = 2 x 384: three double-buffered slabs of
    2048 x 384 bfloat16 are 9.4 MB of VMEM), else the whole width. Where
    three `[hidden, tile]` slabs of that pass `_WIDE_SLABS` (a hidden size
    of 7168: 22 MB at 512), 256 or 128 columns if they divide the width
    and fit `_NARROW_SLABS` (7168 x 256 bfloat16: 11 MB); every shape the
    wide tile fits keeps it."""
    wide = next((tile for tile in (512, 384) if ffn % tile == 0), ffn)
    if 3 * hidden * wide * itemsize <= _WIDE_SLABS:
        return wide
    return next((tile for tile in (256, 128) if ffn % tile == 0
                 and 3 * hidden * tile * itemsize <= _NARROW_SLABS), wide)


def experts_supported(z_shape, w_gate_shape, dtype) -> bool:
    """z [T, H] against W_gate `[L, E, H, F]` (the ungated arm's W_1, whose
    two slabs fit wherever three do): whole 128-lane rows on both
    widths, a 2- or 4-byte dtype, at most 256 experts (the combine weights
    ride one lane register a 128 experts, however many of them a token's
    row fills) and an F tile (`_f_tile`) whose three slabs fit: 8 MB a
    wide tile, 12 MB a narrow one."""
    if len(z_shape) != 2 or len(w_gate_shape) != 4:
        return False
    _, E, H, F = w_gate_shape
    itemsize = jnp.dtype(dtype).itemsize
    tile = _f_tile(F, H, itemsize)
    return (z_shape[1] == H and H % _LANES == 0 and F % _LANES == 0
            and E <= 2 * _LANES and itemsize in (2, 4)
            and 3 * H * tile * itemsize
            <= (_NARROW_SLABS if tile < 384 else _WIDE_SLABS))


def _kernel(layer_ref, z_ref, cw_ref, wg_ref, wu_ref, wd_ref, o_ref):
    del layer_ref                      # read by the index maps
    e = pl.program_id(1)
    f = pl.program_id(2)

    @pl.when((e == 0) & (f == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    z = z_ref[...]                                             # [tt, H]
    g = jnp.dot(z, wg_ref[0, 0], preferred_element_type=jnp.float32)
    u = jnp.dot(z, wu_ref[0, 0], preferred_element_type=jnp.float32)
    cw = cw_ref[...]                            # [tt, 128 or 256]
    lane = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 1)
    col = jnp.sum(jnp.where(lane == e, cw, 0.0), axis=1, keepdims=True)
    hidden = (g * jax.nn.sigmoid(g) * u * col).astype(z.dtype)  # [tt, tf]
    o_ref[...] += jnp.dot(hidden, wd_ref[0, 0],
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tag", "interpret", "topk"))
def _call(z, cw, w_gate, w_up, w_down, layer, tag, interpret, topk=False):
    T, H = z.shape
    _, E, _, F = w_gate.shape
    dtype = w_gate.dtype
    sub = 32 // dtype.itemsize                  # sublanes of one tile
    tt = _TOKEN_TILE if T > _TOKEN_TILE else -(-T // sub) * sub
    t_pad = -(-T // tt) * tt
    tf = _f_tile(F, H, dtype.itemsize)
    lanes = -(-E // _LANES) * _LANES
    zp = jnp.zeros((t_pad, H), dtype).at[:T].set(z.astype(dtype))
    cwp = jnp.zeros((t_pad, lanes), jnp.float32).at[:T, :E].set(
        cw.astype(jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_pad // tt, E, F // tf),
        in_specs=[
            pl.BlockSpec((tt, H), lambda t, e, f, l: (t, 0)),
            pl.BlockSpec((tt, lanes), lambda t, e, f, l: (t, 0)),
            pl.BlockSpec((1, 1, H, tf), lambda t, e, f, l: (l[0], e, 0, f)),
            pl.BlockSpec((1, 1, H, tf), lambda t, e, f, l: (l[0], e, 0, f)),
            pl.BlockSpec((1, 1, tf, H), lambda t, e, f, l: (l[0], e, f, 0)),
        ],
        out_specs=pl.BlockSpec((tt, H), lambda t, e, f, l: (t, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, H), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=6 * t_pad * E * H * F,
            bytes_accessed=((t_pad // tt) * 3 * E * H * F * dtype.itemsize
                            + t_pad * H * (dtype.itemsize + 4)),
            transcendentals=t_pad * E * F),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_topk_experts_" + tag if topk else "moe_top1_experts_" + tag,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), zp, cwp,
      w_gate, w_up, w_down)
    return out[:T]


# -- the grouped form: a window of more than one token tile ------------------

# rows of one tile of the sorted (token, expert) pairs. The matrix unit's time
# for three `[H, tf]` slabs is the time to LOAD them into it up to 256 rows,
# so fewer rows a tile only add tiles that two groups share
GROUP_TILE = _TOKEN_TILE

# what a grouped call leaves free of `_VMEM_LIMIT` beside its buffers, and
# the most rows of one sorted list (their tokens ride in SMEM, 4 bytes each)
_VMEM_SPARE = 6 * 1024 * 1024
_SORTED_ROWS = 16384


def _resident_tokens(w_gate_shape, itemsize: int, k: int) -> int:
    """Tokens of one grouped call over weights `[L, E, H, F]`: the kernel
    keeps their float32 output `[tokens, H]` in VMEM while rows are added
    into it, beside two sets of slabs, two row tiles and one tile of
    products; a window of more tokens goes block by block (every block
    streams the experts it touches)."""
    _, E, H, F = w_gate_shape
    left = (_VMEM_LIMIT - _VMEM_SPARE
            - 6 * H * _f_tile(F, H, itemsize) * itemsize
            - GROUP_TILE * H * (4 + 2 * itemsize))
    return max(min(left // (4 * H), _SORTED_ROWS // min(k, E))
               // GROUP_TILE, 1) * GROUP_TILE


def grouped_visits(counts, xp=jnp):
    """`counts [.., E]`, the live pairs of each held expert in sorted order
    -> (first tile, visits) of each expert, `[.., E]` int32: the sorted list
    is cut into tiles of `GROUP_TILE` rows wherever the groups end, and an
    expert VISITS every tile that holds one of its rows (a tile that two
    groups share is visited once by each). The kernel runs `visits.sum()`
    tiles; `xp` is numpy where the host counts them
    (`serving.moe.grouped_tile_rows`)."""
    ends = xp.cumsum(counts, axis=-1)
    starts = ends - counts
    first = starts // GROUP_TILE
    visits = xp.where(counts > 0, (ends - 1) // GROUP_TILE - first + 1, 0)
    return first.astype(xp.int32), visits.astype(xp.int32)


def _sort_rows(z, cw, k: int, dtype):
    """What the grouped kernel is fed, from z [T, H] and cw [T, E] with at
    most `k` non-zeros a row: the (token, expert) pairs sorted by expert
    and, inside an expert's group, by token, the dead ones (weight zero: a
    token that chose fewer than `k` held experts) behind them. `zs [rows,
    H]` in `dtype`: z's row of each pair; `ws [rows, 1]` its combine weight;
    `token [rows]`; and the plan: `nv` visits live, expert `ve` and tile
    `vt` of each in order (those past `nv` repeat the last live one, so that
    nothing is fetched for them), the groups' bounds `lo [E + 1]`."""
    T, E = cw.shape
    kk = min(k, E)
    rows = -(-T * kk // GROUP_TILE) * GROUP_TILE
    cw = cw.astype(jnp.float32)
    _, ids = jax.lax.top_k(jnp.abs(cw), kk)                      # [T, kk]
    hit = ids[:, :, None] == jnp.arange(E, dtype=jnp.int32)      # [T, kk, E]
    vals = jnp.sum(jnp.where(hit, cw[:, None, :], 0.0), axis=-1)
    counts = jnp.sum(cw != 0, axis=0, dtype=jnp.int32)
    # a stable sort keeps a group in token order
    _, order, weight = jax.lax.sort(
        (jnp.where(vals != 0, ids, E).reshape(-1),
         jnp.arange(T * kk, dtype=jnp.int32), vals.reshape(-1)),
        num_keys=1, is_stable=True)
    pad = rows - T * kk
    token = jnp.pad(order // kk, (0, pad))
    zs = z.astype(dtype)[token]
    ws = jnp.pad(weight, (0, pad))[:, None]
    first, visits = grouped_visits(counts)
    ends = jnp.cumsum(visits)
    nv = ends[-1]
    at = jnp.minimum(jnp.arange(rows // GROUP_TILE + E - 1, dtype=jnp.int32),
                     jnp.maximum(nv - 1, 0))
    ve = jnp.minimum(jnp.sum(ends[None, :] <= at[:, None], axis=1,
                             dtype=jnp.int32), E - 1)
    vt = first[ve] + at - (ends - visits)[ve]
    lo = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])
    return zs, ws, token, (jnp.reshape(nv, (1,)), ve, vt, lo)


def _grouped_kernel(layer_ref, nv_ref, ve_ref, vt_ref, lo_ref, token_ref,
                    z_ref, w_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref,
                    y_ref, sem):
    del layer_ref                      # read by the index maps
    i = pl.program_id(0)
    f = pl.program_id(1)
    last_f = pl.num_programs(1) - 1

    @pl.when((i == 0) & (f == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nv_ref[0])
    def _visit():
        z = z_ref[...]                                         # [tm, H]
        g = jnp.dot(z, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(z, wu_ref[0, 0], preferred_element_type=jnp.float32)
        hidden = (g * jax.nn.sigmoid(g) * u * w_ref[...]).astype(z.dtype)
        y = jnp.dot(hidden, wd_ref[0, 0], preferred_element_type=jnp.float32)

        @pl.when(f == 0)
        def _first():
            y_ref[...] = y

        @pl.when(f > 0)
        def _more():
            y_ref[...] += y

        @pl.when(f == last_f)
        def _add_rows():
            # this expert's rows of the tile, each into its token's row
            e = ve_ref[i]
            base = vt_ref[i] * y_ref.shape[0]

            def add(r, carry):
                t = token_ref[r]
                acc_ref[pl.ds(t, 1), :] += y_ref[pl.ds(r - base, 1), :]
                return carry

            jax.lax.fori_loop(
                jnp.maximum(lo_ref[e], base),
                jnp.minimum(lo_ref[e + 1], base + y_ref.shape[0]), add, 0)

    @pl.when((i == pl.num_programs(0) - 1) & (f == last_f))
    def _write():
        out = pltpu.make_async_copy(acc_ref, o_ref, sem)
        out.start()
        out.wait()


def _grouped_experts(zs, ws, token, plan, tokens, w_gate, w_up, w_down, layer,
                     tag, interpret, topk):
    """The kernel over the sorted rows: float32 `[tokens, H]`, each live row
    through its expert, weighted, and added into its token's row of an
    output that stays in VMEM until the last grid step."""
    rows, H = zs.shape
    _, E, _, F = w_gate.shape
    itemsize = w_gate.dtype.itemsize
    tm = GROUP_TILE
    tf = _f_tile(F, H, itemsize)
    nf = F // tf
    n_visits = plan[1].shape[0]

    def f_at(i, f, nv):
        """The F tile of grid step (i, f): forwards in an even visit,
        backwards in an odd one, so that an expert's second tile starts on
        the slabs its first one ended on; and, for the visits past the last
        live one, where that one ended."""
        odd = jnp.minimum(i, jnp.maximum(nv[0] - 1, 0)) % 2 == 1
        return jnp.where(i < nv[0], jnp.where(odd, nf - 1 - f, f),
                         jnp.where(odd, 0, nf - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_visits, nf),
        in_specs=[
            pl.BlockSpec((tm, H), lambda i, f, l, nv, ve, vt, *_: (vt[i], 0)),
            pl.BlockSpec((tm, 1), lambda i, f, l, nv, ve, vt, *_: (vt[i], 0)),
            pl.BlockSpec((1, 1, H, tf), lambda i, f, l, nv, ve, *_:
                         (l[0], ve[i], 0, f_at(i, f, nv))),
            pl.BlockSpec((1, 1, H, tf), lambda i, f, l, nv, ve, *_:
                         (l[0], ve[i], 0, f_at(i, f, nv))),
            pl.BlockSpec((1, 1, tf, H), lambda i, f, l, nv, ve, *_:
                         (l[0], ve[i], f_at(i, f, nv), 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((tokens, H), jnp.float32),
                        pltpu.VMEM((tm, H), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _grouped_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, H), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=6 * n_visits * tm * H * F,
            bytes_accessed=(3 * E * H * F * itemsize + rows * H * itemsize
                            + tokens * H * 4),
            transcendentals=n_visits * tm * F),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_topk_experts_" + tag if topk else "moe_top1_experts_" + tag,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *plan, token, zs, ws,
      w_gate, w_up, w_down)


@functools.partial(jax.jit,
                   static_argnames=("tag", "interpret", "topk", "k"))
def _grouped_call(z, cw, w_gate, w_up, w_down, layer, tag, interpret, topk,
                  k):
    T, H = z.shape
    E = w_gate.shape[1]
    itemsize = w_gate.dtype.itemsize
    sub = 32 // itemsize                        # sublanes of one tile
    block = _resident_tokens(w_gate.shape, itemsize, k)

    def one(z, cw):
        zs, ws, token, plan = _sort_rows(z, cw, k, w_gate.dtype)
        return _grouped_experts(zs, ws, token, plan, len(z), w_gate, w_up,
                                w_down, layer, tag, interpret, topk)

    # whole sublane tiles of tokens (their weights zero: no pair is made of
    # them); more than one call keeps resident go block by block, traced
    # once however many (a Program is built on stand-in row counts of
    # millions: `ops/registry._DYN`)
    blocks = -(-T // block)
    t_pad = -(-T // sub) * sub if blocks == 1 else blocks * block
    zp = jnp.zeros((t_pad, H), z.dtype).at[:T].set(z)
    cwp = jnp.zeros((t_pad, E), cw.dtype).at[:T].set(cw)
    if blocks == 1:
        return one(zp, cwp)[:T]
    # (the barrier keeps the kernel a call of its own: fused with the
    # loop's write of its block it is refused its VMEM)
    out = jax.lax.map(lambda a: jax.lax.optimization_barrier(one(*a)),
                      (zp.reshape(blocks, block, H),
                       cwp.reshape(blocks, block, E)))
    return out.reshape(t_pad, H)[:T]


def _reference(z, cw, w_gate, w_up, w_down, layer=0, tag="decode"):
    """The same sum in plain jnp (the numeric oracle and the path off the
    chip): every expert over every token, weighted."""
    del tag
    wg = jax.lax.dynamic_index_in_dim(w_gate, layer, 0, keepdims=False)
    wu = jax.lax.dynamic_index_in_dim(w_up, layer, 0, keepdims=False)
    wd = jax.lax.dynamic_index_in_dim(w_down, layer, 0, keepdims=False)
    zc = z.astype(wg.dtype)
    g = jnp.einsum("th,ehf->etf", zc, wg, preferred_element_type=jnp.float32)
    u = jnp.einsum("th,ehf->etf", zc, wu, preferred_element_type=jnp.float32)
    hidden = g * jax.nn.sigmoid(g) * u * cw.astype(jnp.float32).T[:, :, None]
    return jnp.einsum("etf,efh->th", hidden.astype(wg.dtype), wd,
                      preferred_element_type=jnp.float32)


def _workbench_register():
    from . import workbench

    return workbench.register_kernel(
        "moe_top1_experts",
        reference=_reference,
        supported=lambda z, w: experts_supported(z, w, jnp.bfloat16),
        decision_op="moe_experts",
        equivalence_test="test_moe_experts_pallas_matches_reference",
        note="top-1 SwiGLU experts over stacked [L, E, ...] weights; layer "
             "index by scalar prefetch, every held expert streamed once (a "
             "call of more than 256 rows: its pairs sorted by expert, the "
             "touched experts streamed once)")


@_workbench_register()
def moe_top1_experts(z, cw, w_gate, w_up, w_down, layer=0, tag="decode"):
    """z [T, H], cw [T, E] (combine weight of token t for held expert e),
    weights `[L, E, H, F]`, `[L, E, H, F]`, `[L, E, F, H]`, `layer` a scalar
    int. Returns float32 [T, H]. Callers gate on `experts_supported`. More
    rows than one token tile go through the grouped form, ONE non-zero a
    row of `cw`."""
    layer = jnp.asarray(layer, jnp.int32)
    if z.shape[0] > _TOKEN_TILE:
        return _grouped_call(z, cw, w_gate, w_up, w_down, layer, str(tag),
                             bool(INTERPRET), False, 1)
    return _call(z, cw, w_gate, w_up, w_down, layer, str(tag),
                 bool(INTERPRET))


def moe_topk_experts(z, cw, w_gate, w_up, w_down, layer=0, tag="decode",
                     k: int | None = None):
    """The same kernel under another name in the trace
    (`moe_topk_experts_<tag>`): a top-k family's call, so that a reader of
    `^moe_top1_experts` keeps finding the top-1 family's calls and nothing
    else. `k`: the most non-zeros a row of `cw` has (the router's experts a
    token); the grouped form sizes its pair list by it, so a call of more
    rows than one token tile has to say it."""
    layer = jnp.asarray(layer, jnp.int32)
    if z.shape[0] > _TOKEN_TILE:
        if k is None:
            raise ValueError(
                f"moe_topk_experts: {z.shape[0]} rows take the grouped "
                f"form, which needs k, the most non-zeros a row of cw has")
        return _grouped_call(z, cw, w_gate, w_up, w_down, layer, str(tag),
                             bool(INTERPRET), True, int(k))
    return _call(z, cw, w_gate, w_up, w_down, layer, str(tag),
                 bool(INTERPRET), topk=True)


# -- the ungated arm: W_2 relu(W_1 u)^2 --------------------------------------


def _relu2_kernel(layer_ref, z_ref, cw_ref, w1_ref, w2_ref, o_ref):
    del layer_ref                      # read by the index maps
    e = pl.program_id(1)
    f = pl.program_id(2)

    @pl.when((e == 0) & (f == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    z = z_ref[...]                                             # [tt, Z]
    g = jnp.maximum(jnp.dot(z, w1_ref[0, 0],
                            preferred_element_type=jnp.float32), 0.0)
    cw = cw_ref[...]                            # [tt, 128 or 256]
    lane = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 1)
    col = jnp.sum(jnp.where(lane == e, cw, 0.0), axis=1, keepdims=True)
    hidden = (g * g * col).astype(z.dtype)                     # [tt, tf]
    o_ref[...] += jnp.dot(hidden, w2_ref[0, 0],
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tag", "interpret"))
def _relu2_call(z, cw, w1, w2, layer, tag, interpret):
    T, Z = z.shape
    _, E, _, F = w1.shape
    dtype = w1.dtype
    sub = 32 // dtype.itemsize                  # sublanes of one tile
    tt = _TOKEN_TILE if T > _TOKEN_TILE else -(-T // sub) * sub
    t_pad = -(-T // tt) * tt
    tf = _f_tile(F, Z, dtype.itemsize)
    lanes = -(-E // _LANES) * _LANES
    zp = jnp.zeros((t_pad, Z), dtype).at[:T].set(z.astype(dtype))
    cwp = jnp.zeros((t_pad, lanes), jnp.float32).at[:T, :E].set(
        cw.astype(jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_pad // tt, E, F // tf),
        in_specs=[
            pl.BlockSpec((tt, Z), lambda t, e, f, l: (t, 0)),
            pl.BlockSpec((tt, lanes), lambda t, e, f, l: (t, 0)),
            pl.BlockSpec((1, 1, Z, tf), lambda t, e, f, l: (l[0], e, 0, f)),
            pl.BlockSpec((1, 1, tf, Z), lambda t, e, f, l: (l[0], e, f, 0)),
        ],
        out_specs=pl.BlockSpec((tt, Z), lambda t, e, f, l: (t, 0)),
    )
    out = pl.pallas_call(
        _relu2_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, Z), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=4 * t_pad * E * Z * F,
            bytes_accessed=((t_pad // tt) * 2 * E * Z * F * dtype.itemsize
                            + t_pad * Z * (dtype.itemsize + 4)),
            transcendentals=0),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_relu2_experts_" + tag,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), zp, cwp, w1, w2)
    return out[:T]


def _relu2_reference(z, cw, w1, w2, layer=0, tag="decode"):
    """The ungated sum in plain jnp (the numeric oracle and the path off
    the chip): every expert over every token, weighted."""
    del tag
    a = jax.lax.dynamic_index_in_dim(w1, layer, 0, keepdims=False)
    b = jax.lax.dynamic_index_in_dim(w2, layer, 0, keepdims=False)
    g = jnp.maximum(jnp.einsum("tz,ezf->etf", z.astype(a.dtype), a,
                               preferred_element_type=jnp.float32), 0.0)
    hidden = g * g * cw.astype(jnp.float32).T[:, :, None]
    return jnp.einsum("etf,efz->tz", hidden.astype(a.dtype), b,
                      preferred_element_type=jnp.float32)


def _relu2_register():
    from . import workbench

    return workbench.register_kernel(
        "moe_relu2_experts",
        reference=_relu2_reference,
        supported=lambda z, w: experts_supported(z, w, jnp.bfloat16),
        decision_op="moe_experts",
        equivalence_test="test_moe_relu2_experts_pallas_matches_reference",
        note="top-k ungated experts W_2 relu(W_1 u)^2 over stacked [L, E, "
             "...] weights in a latent; layer index by scalar prefetch, "
             "every held expert streamed once")


@_relu2_register()
def moe_relu2_experts(z, cw, w1, w2, layer=0, tag="decode"):
    """z [T, Z], cw [T, E] (combine weight of token t for held expert e),
    weights `[L, E, Z, F]` and `[L, E, F, Z]`, `layer` a scalar int. Returns
    float32 [T, Z]. Callers gate on `experts_supported(z.shape, w1.shape,
    dtype)`."""
    return _relu2_call(z, cw, w1, w2, jnp.asarray(layer, jnp.int32),
                       str(tag), bool(INTERPRET))
