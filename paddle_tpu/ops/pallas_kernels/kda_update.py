"""One decode token of a Kimi-Delta linear-attention layer, in place in the
pool of recurrent states: a Pallas TPU kernel.

What it computes, for every live row b of a decode step (slot `idx[b]` of
the pool) and head h, with S `[K, V]` float32 (key channels on the sublanes,
value channels on the lanes):

    S' = Diag(a) S                      the decay, one value a KEY channel
    u  = S'^T k                         what the decayed state holds for k
    S  <- S' + k (x) beta (v - u)       the delta rule's rank-one write
    o  = S^T q

`a`, `k` and `q` are a value a SUBLANE, `v`, `beta` and `o` a value a lane.
The three sublane vectors of the `_HEAD_BLOCK` heads a grid step holds
arrive as rows of ONE `[3 * heads, K]` operand (k, q, a of head 0, then of
head 1, ..), are padded to a whole `[128, 128]` tile and transposed ONCE a
grid step; a head then reads its three columns and broadcasts them over the
lanes. The two contractions run down the sublanes.

Why a kernel: the step reads and writes a row's whole state (2 MB a layer at
32 heads of 128 x 128 float32) and does a dozen flops a value: a stream of
the pool through the chip. XLA's form gathers the rows' states, updates the
copy and scatters it back. Here a grid step DMAs `_HEAD_BLOCK` heads of ONE
slot (the slot a prefetched scalar), updates them in VMEM and writes them
back where they came from (`input_output_aliases`): S is read once and
written once. Live rows only, by `ssm_update`'s rule: the first `n_live`
rows of a step carry a request, and a padding row's grid steps name the
last live row's last blocks, so nothing moves for them and their `o` is
zeros.

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
# heads of one slot a grid step holds: 8 x 64 KB in, as much out, twice for
# the double buffer
_HEAD_BLOCK = 8


def update_supported(pool_shape, key_dim: int) -> bool:
    """A pool `[rows, heads * K, V]` of float32 whose head is one `[128,
    128]` slab (K = V = 128: whole tiles, and a head's three sublane vectors
    are whole lane rows of the transposed operand) and whose heads come in
    whole blocks of `_HEAD_BLOCK`."""
    if len(pool_shape) != 3 or key_dim != _LANES \
            or pool_shape[2] != _LANES or pool_shape[1] % key_dim:
        return False
    return (pool_shape[1] // key_dim) % _HEAD_BLOCK == 0


def _kernel(idx_ref, n_ref, s_ref, kqa_ref, v_ref, beta_ref, so_ref, o_ref):
    del idx_ref                        # read by the index maps
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _update():
        K = kqa_ref.shape[3]
        kqa = kqa_ref[0, 0]                                 # [3 * hb, K]
        cols = jnp.concatenate(
            [kqa, jnp.zeros((_LANES - kqa.shape[0], K), jnp.float32)],
            axis=0).T                                       # [K, 128]
        for h in range(s_ref.shape[1] // K):
            rows = slice(h * K, (h + 1) * K)
            kcol = cols[:, 3 * h:3 * h + 1]                 # [K, 1]
            qcol = cols[:, 3 * h + 1:3 * h + 2]
            acol = cols[:, 3 * h + 2:3 * h + 3]
            s1 = acol * s_ref[0, rows]                      # [K, V]
            u = jnp.sum(s1 * kcol, axis=0, keepdims=True)   # [1, V]
            d = beta_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - u)
            s2 = s1 + kcol * d
            so_ref[0, rows] = s2
            o_ref[0, h:h + 1, :] = jnp.sum(s2 * qcol, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _padding():
        # the state blocks in VMEM are the last live row's: left alone
        o_ref[...] = jnp.zeros_like(o_ref)


def _specs(blocks: int, hb: int, K: int, V: int):
    """The BlockSpecs of a grid `(rows, blocks)`: (state, sublane vectors,
    lane vectors, o). A grid step of a padding row (`b >= n[0]`) names, for
    everything but `o`, the blocks of grid step `(n[0] - 1, blocks - 1)`,
    the last one that did any work: a block index that repeats moves
    nothing (`ssm_update._specs`)."""

    def at(b, j, n):
        return jnp.minimum(b, n[0] - 1), jnp.where(b < n[0], j, blocks - 1)

    def state(b, j, idx, n):
        b, j = at(b, j, n)
        return idx[b], j, 0

    def columns(b, j, idx, n):
        return (*at(b, j, n), 0, 0)

    def lanes(b, j, idx, n):
        return (*at(b, j, n), 0)

    return (pl.BlockSpec((1, hb * K, V), state),
            pl.BlockSpec((1, 1, 3 * hb, K), columns),
            pl.BlockSpec((1, hb, V), lanes),
            pl.BlockSpec((1, hb, V), lambda b, j, idx, n: (b, j, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(pool, idx, n_live, q, k, v, a, beta, interpret):
    rows, HK, V = pool.shape
    B, H, K = k.shape
    hb = _HEAD_BLOCK
    f32 = jnp.float32
    # k, q, a of a head one under the other, a block of heads an operand row
    kqa = jnp.stack([k.astype(f32), q.astype(f32), a.astype(f32)],
                    axis=2).reshape(B, H // hb, 3 * hb, K)
    state, columns, lanes, o_rows = _specs(H // hb, hb, K, V)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // hb),
        in_specs=[state, columns, lanes, lanes],
        out_specs=[state, o_rows],
    )
    new_pool, o = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((B, H, V), f32)],
        # operands 0 and 1 are the prefetched slot list and live count
        input_output_aliases={2: 0},
        cost_estimate=pl.CostEstimate(
            flops=8 * B * H * K * V, transcendentals=0,
            bytes_accessed=2 * B * H * K * V * 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_update",
    )(jnp.clip(idx.astype(jnp.int32), 0, rows - 1), live_count(n_live, B),
      pool, kqa, v.astype(f32),
      jnp.broadcast_to(beta.astype(f32)[:, :, None], (B, H, V)))
    return new_pool, o


def _reference(pool, idx, q, k, v, a, beta, n_live=None):
    """The same update in plain jnp (the numeric oracle and the path off
    the chip): the rows' states gathered, updated and scattered back. As in
    the kernel, only the first `n_live` rows (None: all) are live: a padding
    row's slot is left as it was and its `o` is zeros."""
    B, H, K = k.shape
    f32 = jnp.float32
    live = jnp.arange(B) < live_count(n_live, B)
    idx = jnp.clip(idx.astype(jnp.int32), 0, pool.shape[0] - 1)
    q, k, v, a, beta = (x.astype(f32) for x in (q, k, v, a, beta))
    s1 = a[..., None] * pool[idx].reshape(B, H, K, -1)
    u = jnp.sum(s1 * k[..., None], axis=2)                      # [B, H, V]
    s2 = s1 + k[..., None] * (beta[..., None] * (v - u))[:, :, None, :]
    o = jnp.sum(s2 * q[..., None], axis=2)
    # a padding row is sent past the pool's end, where the scatter drops it
    return pool.at[jnp.where(live, idx, pool.shape[0])].set(
        s2.reshape((B,) + pool.shape[1:]), mode="drop"), \
        jnp.where(live[:, None, None], o, 0.0)


def _workbench_register():
    from . import workbench

    return workbench.register_kernel(
        "kda_decode_update",
        reference=_reference,
        supported=update_supported,
        decision_op="ssm_update",
        equivalence_test="test_kda_decode_update_pallas_matches_reference",
        note="one token of a Kimi-Delta layer (decay by key channel, then "
             "the delta rule's rank-one write) in place in the slot pool "
             "[rows, heads * 128, 128] float32; slot by scalar prefetch, "
             "the pool aliased to the output, live rows only")


@_workbench_register()
def kda_decode_update(pool, idx, q, k, v, a, beta, n_live=None):
    """pool `[rows, H * K, V]` float32, idx [B] (the row of each decode
    row's state), q, k, a [B, H, K] (the scaled query, the normalised key,
    the decay in (0, 1] a key channel), v [B, H, V], beta [B, H] (the
    step), n_live (an int32 scalar, traced or not; None: B) the count of
    live rows, which come first. Returns (the pool with the live rows'
    slots updated, o [B, H, V] float32, zeros in a padding row). Callers
    gate on `update_supported`."""
    return _call(pool, idx, n_live, q, k, v, a, beta, bool(INTERPRET))
