"""One decode token of a Mamba-2 mixer's depthwise causal convolution, its
tail updated in place in the pool of tails: a Pallas TPU kernel.

What it computes, for every row b of a decode step (slot `idx[b]` of the
pool; `tail` the slot's K - 1 rows of C channels, the pre-convolution rows
of the K - 1 tokens before this one; `x[b]` this token's row):

    out = silu(bias + sum_{j < K-1} w_j * tail_j + w_{K-1} * x[b])
    tail <- (tail_1, .., tail_{K-2}, x[b])

in the order `parallel_ssm_ops.causal_conv_fn` sums, float32 throughout.

Why a kernel: the step moves a row's tail (123 KB at three rows of 10,240
channels) and does eight flops a channel. XLA's form of it, a gather of the
rows' tails and a scatter of the new ones, passed over the WHOLE pool in
every scatter while a slot was one sublane of a two-dimensional `[800,
30720]` pool (98 MB each way, 0.37 ms a scatter and six of them a step of
five mixers, 3.17 ms with the copies around them, where the 128 rows' tails
are 15.7 MB a mixer), and on the pool as it is now still took 1.23 ms a
step in gathers, relayouts and scatters of whole slots; this kernel takes
0.38 (75 us a mixer at 128 rows; my chip runs, PERF.md, PR 44). A grid step
DMAs ONE slot's tail (the slot is a prefetched scalar the BlockSpec index
maps read, as in `ssm_update`), convolves the token in VMEM and writes the
new tail back where the old one came from (`input_output_aliases`): the
other slots are never read. Nor are the padding rows' (`n_live`, a second
prefetched scalar, counts the live rows, which come first): a grid step of
a padding row names the last live row's blocks, which moves nothing, and
writes its `y` as zeros, as in `ssm_update` (PR 46).

A block has to be whole (8, 128) tiles, so the pool keeps a slot as
`[tail_width / 128, 128]` (`kv_cache.state_pool_shapes`): channels run
along the lanes, 128 to a sublane row, and tail row j is the sublane rows
`j * C / 128 .. (j + 1) * C / 128 - 1`. The token's row, the weights
(`[K, C / 128, 128]`, fetched once: their block never moves) and the bias
come in the same form, so the body is elementwise on whole tiles.

Forward only: serving never differentiates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm_update import live_count

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False

_LANES = 128
_SUBLANES = 8


def update_supported(pool_shape, taps: int) -> bool:
    """A pool `[rows, (taps - 1) * C / 128, 128]` of float32 whose tail rows
    are whole (8, 128) tiles each (`C % 1024 == 0`)."""
    return (len(pool_shape) == 3 and pool_shape[2] == _LANES and taps >= 2
            and pool_shape[1] % (_SUBLANES * (taps - 1)) == 0)


def _kernel(idx_ref, n_ref, tail_ref, x_ref, w_ref, b_ref, tail_out_ref,
            y_ref):
    del idx_ref                        # read by the index maps
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _update():
        K, Cb = w_ref.shape[0], x_ref.shape[1]
        x = x_ref[0]
        out = b_ref[0]
        for j in range(K - 1):
            out = out + w_ref[j] * tail_ref[0, j * Cb:(j + 1) * Cb]
        out = out + w_ref[K - 1] * x
        y_ref[0] = out * jax.nn.sigmoid(out)
        if K > 2:
            tail_out_ref[0, :(K - 2) * Cb] = tail_ref[0, Cb:]
        tail_out_ref[0, (K - 2) * Cb:] = x

    @pl.when(jnp.logical_not(live))
    def _padding():
        # the tail in VMEM is the last live row's: left alone
        y_ref[...] = jnp.zeros_like(y_ref)


def _specs(T: int, Cb: int, lanes: int):
    """The BlockSpecs of a grid `(rows,)`: (slot, token, y_row). A padding
    row's grid step (`b >= n[0]`) names the slot and the token of the last
    live row, `n[0] - 1`: a block index that repeats moves nothing.
    `y_row` (the output `y`) is every row's own."""

    def slot(b, idx, n):
        return idx[jnp.minimum(b, n[0] - 1)], 0, 0

    return (pl.BlockSpec((1, T, lanes), slot),
            pl.BlockSpec((1, Cb, lanes),
                         lambda b, idx, n: (jnp.minimum(b, n[0] - 1), 0, 0)),
            pl.BlockSpec((1, Cb, lanes), lambda b, idx, n: (b, 0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(pool, idx, n_live, x, conv_w, conv_b, interpret):
    rows, T, lanes = pool.shape
    B, C = x.shape
    K, Cb = conv_w.shape[1], C // lanes
    slot, token, y_row = _specs(T, Cb, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[slot, token,
                  pl.BlockSpec((K, Cb, lanes), lambda b, idx, n: (0, 0, 0)),
                  pl.BlockSpec((1, Cb, lanes), lambda b, idx, n: (0, 0, 0))],
        out_specs=[slot, y_row],
    )
    new_pool, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((B, Cb, lanes), jnp.float32)],
        # operands 0 and 1 are the prefetched slot list and live count
        input_output_aliases={2: 0},
        cost_estimate=pl.CostEstimate(
            flops=(2 * K + 4) * B * C, transcendentals=B * C,
            bytes_accessed=(2 * (K - 1) + 2) * B * C * 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="conv_decode_update",
    )(jnp.clip(idx.astype(jnp.int32), 0, rows - 1), live_count(n_live, B),
      pool, x.astype(jnp.float32).reshape(B, Cb, lanes),
      conv_w.astype(jnp.float32).T.reshape(K, Cb, lanes),
      conv_b.astype(jnp.float32).reshape(1, Cb, lanes))
    return new_pool, y.reshape(B, C)


def _reference(pool, idx, x, conv_w, conv_b, n_live=None):
    """The same update in plain jnp (the numeric oracle and the path off
    the chip, for a pool of either form): the rows' tails gathered, the
    token convolved behind them by `causal_conv_fn`, the new tails
    scattered back. As in the kernel, only the first `n_live` rows (None:
    all) are live: a padding row's slot is left as it was and its `y` is
    zeros."""
    from ..parallel_ssm_ops import causal_conv_fn

    B, C = x.shape
    live = jnp.arange(B) < live_count(n_live, B)
    idx = jnp.clip(idx.astype(jnp.int32), 0, pool.shape[0] - 1)
    y, tail = causal_conv_fn(x.astype(jnp.float32)[:, None],
                             pool[idx].reshape(B, -1, C), conv_w, conv_b)
    # a padding row is sent past the pool's end, where the scatter drops it
    return pool.at[jnp.where(live, idx, pool.shape[0])].set(
        tail.reshape((B,) + pool.shape[1:]), mode="drop"), \
        jnp.where(live[:, None], y[:, 0], 0.0)


def _workbench_register():
    from . import workbench

    return workbench.register_kernel(
        "conv_decode_update",
        reference=_reference,
        supported=update_supported,
        decision_op="ssm_update",
        equivalence_test="test_conv_decode_update_pallas_matches_reference",
        note="one token of a Mamba-2 mixer's causal convolution, its tail "
             "moved on in place in the slot pool [rows, tail / 128, 128] "
             "float32; slot by scalar prefetch, the pool aliased to the "
             "output")


@_workbench_register()
def conv_decode_update(pool, idx, x, conv_w, conv_b, n_live=None):
    """pool `[rows, (K - 1) * C / 128, 128]` float32, idx [B] (the row of
    each decode row's tail), x [B, C] (the token's pre-convolution row),
    conv_w [C, K], conv_b [C], n_live (an int32 scalar, traced or not;
    None: B) the count of live rows, which come first. Returns (the pool
    with the live rows' slots moved on one token, silu(conv) [B, C]
    float32, zeros in a padding row). Callers gate on `update_supported`."""
    return _call(pool, idx, n_live, x, conv_w, conv_b, bool(INTERPRET))
