"""One decode token of a Mamba-2 state-space layer, in place in the pool of
recurrent states: a Pallas TPU kernel.

What it computes, for every row b of a decode step (slot `idx[b]` of the
pool, head h, group g(h)):

    S <- a[b, h] * S + B[b, g] (x) dtx[b, h]        S: [N, P] float32
    y[b, h] = C[b, g] . S  (over N)

`a` is the token's decay, `dtx` its input times its step (`dt * x`), `B`
and `C` the group's input and output maps. The skip term `D * x` and
everything before and after are the caller's (`parallel_ssm_ops`).

Why a kernel: the step reads and writes a row's whole state (4 MB a layer
at 32 heads of 128 x 256 float32) and does four flops a value, so it is a
stream of the pool through the chip and nothing else. XLA's form of it
gathers the rows' states out of the pool, updates the copy and scatters it
back: three passes. Here a grid step DMAs `_HEAD_BLOCK` heads of ONE slot
(the slot is a prefetched scalar the BlockSpec index maps read), updates
them in VMEM and writes them back where they came from (`input_output_
aliases`): the pool moves once each way.

A step's rows are a ROW BUCKET: the first `n_live` of them carry a request,
the rest are padding (`engine._decode_once`, `_state_feed`: on the scratch
slot). `n_live` is a second prefetched scalar, and a padding row moves
nothing: for its grid steps every index map but `y`'s names the blocks of
the LAST LIVE row's last grid step (`_specs`), so the pipeline sees a block
index that does not change and issues no DMA in and no write-back, and the
body only writes the row's `y` as zeros. Before (PR 43 to 45) a padding row
streamed the scratch slot's 4 MB in and out like a live one. On the chip a
call at a bucket of 128 takes 0.14 ms + 11.8 us a LIVE row (1.65 ms at
128, 1.32 at 100, 0.90 at 64; the parent 1.66 at each), and the Nemotron
cell's decode program 22.5 / 20.6 / 18.1 ms a step (PERF.md, PR 46): what
a server gains while its bucket is part filled. The scratch slot is never
written here; nothing reads it.

A slot's states are kept as ONE `[heads * N, P]` slab, head after head, the
state dimension on the sublanes (a pool of three dimensions: a gather or a
scatter of whole slots, which a prefill window does, then has no pair of
minor dimensions XLA could ask for the other way round; at `[rows, heads,
N, P]` it transposed the whole pool into the layout its scan preferred and
back, in every window). `dtx` and
`a` (a value a lane) then broadcast over sublanes, which costs nothing, and
`B` and `C` (a value a sublane) are made once a grid step by transposing a
`[P, N]` broadcast; the contraction with `C` runs down the sublanes.

Heads NARROWER than the lanes (64 wide over a state of 128: P < 128) lie
`pack = 128 / P` heads of one group SIDE BY SIDE on the lanes: a slot is
`[heads / pack * N, pack * P]`, slab j the heads `pack * j .. pack * j +
pack - 1`, head `pack * j + i` on lanes `i * P .. (i + 1) * P - 1`. Heads
that share a slab share a group, so `B` and `C` stay a value a sublane for
the whole slab, `a` and `dtx` stay a value a lane (a head's `a` over its P
lanes), and the kernel's arithmetic is the one above on `heads / pack`
slabs of 128 lanes: the same body, whole (8, 128) tiles, no transpose and
no lane reduction. Why not the state on the lanes (`[heads * P, N]`, the
contraction with `C` a lane reduction): `dtx` would be a value a SUBLANE,
which a `[B, heads, P]` operand reaches only through a transpose in VMEM a
grid step or a lane-sparse `[B, heads * P, 1]` operand in HBM (128 x its
bytes under the (8, 128) tiling), and `y` would leave the kernel as a
column; that form was not built and not measured (PR 43). The wrapper
derives `pack` from the operands' shapes (`pool.shape[2] / dtx.shape[2]`);
what a window's scan reads and writes goes through `pack_state` /
`unpack_state`.

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
# heads of one slot a grid step holds: 8 x 128 KB in, as much out, twice
# for the double buffer
_HEAD_BLOCK = 8


def update_supported(pool_shape, state: int, heads_per_group: int) -> bool:
    """A pool `[rows, slabs * N, lanes]` of float32 whose `[N, lanes]` slab
    (a head, or `pack` narrow heads side by side: the module docstring) is
    whole (8, 128) tiles and whose blocks of `_HEAD_BLOCK` slabs lie inside
    one group; `heads_per_group` counts a group's SLABS. A pool of heads
    narrower than the lanes that was not packed is refused."""
    if len(pool_shape) != 3 or state <= 0 or pool_shape[1] % state:
        return False
    H, P = pool_shape[1] // state, pool_shape[2]
    return (P == _LANES and state % _LANES == 0 and H % _HEAD_BLOCK == 0
            and heads_per_group % _HEAD_BLOCK == 0)


def live_count(n_live, rows: int):
    """`n_live` (None: every row) as the int32 [1] both arms take, held to
    1 .. rows: a step with no live row treats row 0 as one."""
    n = rows if n_live is None else n_live
    return jnp.clip(jnp.asarray(n, jnp.int32), 1, rows).reshape(1)


def _kernel(idx_ref, n_ref, s_ref, a_ref, dtx_ref, b_ref, c_ref, so_ref,
            y_ref):
    del idx_ref                        # read by the index maps
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _update():
        N, P = b_ref.shape[3], s_ref.shape[2]
        # B and C, a value a sublane, on every lane
        bcol = jnp.broadcast_to(b_ref[0, 0], (P, N)).T          # [N, P]
        ccol = jnp.broadcast_to(c_ref[0, 0], (P, N)).T
        for h in range(s_ref.shape[1] // N):
            rows = slice(h * N, (h + 1) * N)
            s_new = a_ref[0, h:h + 1, :] * s_ref[0, rows] \
                + bcol * dtx_ref[0, h:h + 1, :]
            so_ref[0, rows] = s_new
            y_ref[0, h:h + 1, :] = jnp.sum(s_new * ccol, axis=0,
                                           keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _padding():
        # the state blocks in VMEM are the last live row's: left alone
        y_ref[...] = jnp.zeros_like(y_ref)


def _specs(blocks: int, hb: int, N: int, P: int, per_group: int):
    """The BlockSpecs of a grid `(rows, blocks)`: (state, head_rows, group,
    y_rows). A grid step `(b, j)` of a live row names its own blocks. One
    of a padding row (`b >= n[0]`) names, for the state, `a`, `dtx`, `B`
    and `C`, the blocks of grid step `(n[0] - 1, blocks - 1)`, the last one
    that did any work: a block index that repeats moves nothing. `y_rows`
    (the output `y`) is every row's own."""

    def at(b, j, n):
        return jnp.minimum(b, n[0] - 1), jnp.where(b < n[0], j, blocks - 1)

    def state(b, j, idx, n):
        b, j = at(b, j, n)
        return idx[b], j, 0

    def head_rows(b, j, idx, n):
        return (*at(b, j, n), 0)

    def group(b, j, idx, n):
        b, j = at(b, j, n)
        return b, j * hb // per_group, 0, 0

    return (pl.BlockSpec((1, hb * N, P), state),
            pl.BlockSpec((1, hb, P), head_rows),
            pl.BlockSpec((1, 1, 1, N), group),
            pl.BlockSpec((1, hb, P), lambda b, j, idx, n: (b, j, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(pool, idx, n_live, a, dtx, bmat, cmat, interpret):
    rows, HN, P = pool.shape
    B, G, N = bmat.shape
    H = HN // N                 # slabs: heads, or packs of narrow heads
    pack = a.shape[1] // H
    hb = _HEAD_BLOCK
    per_group = H // G
    lanes = jnp.broadcast_to(a.astype(jnp.float32)[:, :, None],
                             (B, H * pack, P // pack)).reshape(B, H, P)
    state, head_rows, group, y_rows = _specs(H // hb, hb, N, P, per_group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // hb),
        in_specs=[state, head_rows, head_rows, group, group],
        out_specs=[state, y_rows],
    )
    new_pool, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((B, H, P), jnp.float32)],
        # operands 0 and 1 are the prefetched slot list and live count
        input_output_aliases={2: 0},
        cost_estimate=pl.CostEstimate(
            flops=5 * B * H * N * P, transcendentals=0,
            bytes_accessed=2 * B * H * N * P * 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_decode_update",
    )(jnp.clip(idx.astype(jnp.int32), 0, rows - 1), live_count(n_live, B),
      pool, lanes,
      dtx.astype(jnp.float32).reshape(B, H, P),
      bmat.astype(jnp.float32).reshape(B, G, 1, N),
      cmat.astype(jnp.float32).reshape(B, G, 1, N))
    return new_pool, y.reshape(dtx.shape)


def pack_state(s, pack: int):
    """States `[B, heads, N, P]` as slots of the pool: `[B, heads / pack *
    N, pack * P]`, `pack` heads side by side on the lanes. Written as a
    concatenation of the heads' lanes, not as a transpose: a transpose of
    the rows a window writes made XLA ask for the WHOLE pool in the
    transposed rows' layout and copy it there and back in every window
    (3.4 GB each way at 800 slots of 4 MB; described-v5e HLO, PR 43)."""
    B, H, N, P = s.shape
    if pack == 1:
        return s.reshape(B, H * N, P)
    return jnp.concatenate([s[:, i::pack] for i in range(pack)],
                           axis=-1).reshape(B, H // pack * N, pack * P)


def unpack_state(slots, heads: int, state: int):
    """`pack_state`'s inverse: slots `[B, heads / pack * N, pack * P]` ->
    `[B, heads, N, P]`."""
    B, rows, lanes = slots.shape
    pack = heads * state // rows
    if pack == 1:
        return slots.reshape(B, heads, state, lanes)
    P = lanes // pack
    slabs = slots.reshape(B, heads // pack, state, lanes)
    return jnp.stack([slabs[..., i * P:(i + 1) * P] for i in range(pack)],
                     axis=2).reshape(B, heads, state, P)


def _reference(pool, idx, a, dtx, bmat, cmat, n_live=None):
    """The same update in plain jnp (the numeric oracle and the path off
    the chip): the rows' states gathered, updated and scattered back. As
    in the kernel, only the first `n_live` rows (None: all) are live: a
    padding row's slot is left as it was and its `y` is zeros."""
    (B, G, N), H = bmat.shape, a.shape[1]
    pack = H * N // pool.shape[1]
    live = jnp.arange(B) < live_count(n_live, B)
    idx = jnp.clip(idx.astype(jnp.int32), 0, pool.shape[0] - 1)
    bh = jnp.repeat(bmat.astype(jnp.float32), H // G, axis=1)   # [B, H, N]
    ch = jnp.repeat(cmat.astype(jnp.float32), H // G, axis=1)
    s_new = a.astype(jnp.float32)[:, :, None, None] \
        * unpack_state(pool[idx], H, N) \
        + bh[:, :, :, None] * dtx.astype(jnp.float32)[:, :, None, :]
    y = jnp.sum(s_new * ch[:, :, :, None], axis=2)
    # a padding row is sent past the pool's end, where the scatter drops it
    return pool.at[jnp.where(live, idx, pool.shape[0])].set(
        pack_state(s_new, pack), mode="drop"), \
        jnp.where(live[:, None, None], y, 0.0)


def _workbench_register():
    from . import workbench

    return workbench.register_kernel(
        "ssm_decode_update",
        reference=_reference,
        supported=update_supported,
        decision_op="ssm_update",
        equivalence_test="test_ssm_decode_update_pallas_matches_reference",
        note="one token of a Mamba-2 layer in place in the slot pool "
             "[rows, heads * N, P] float32; slot by scalar prefetch, the "
             "pool aliased to the output")


@_workbench_register()
def ssm_decode_update(pool, idx, a, dtx, bmat, cmat, n_live=None):
    """pool `[rows, H * N, P]` float32 (or, heads narrower than the lanes,
    `[rows, H / pack * N, pack * P]`: the module docstring), idx [B] (the
    row of each decode row's state), a [B, H] (decay), dtx [B, H, P], bmat
    and cmat [B, G, N], n_live (an int32 scalar, traced or not; None: B)
    the count of live rows, which come first. Returns (the pool with the
    live rows' slots updated, y [B, H, P] float32, zeros in a padding
    row). Callers gate on `update_supported`."""
    return _call(pool, idx, n_live, a, dtx, bmat, cmat, bool(INTERPRET))
