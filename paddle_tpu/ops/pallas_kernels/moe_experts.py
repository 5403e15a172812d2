"""Expert layer over stacked expert weights, top-1 or top-k: a Pallas TPU
kernel.

What it computes: `out[t] = sum_e cw[t, e] * W_down[e] (silu(W_gate[e] z_t)
* (W_up[e] z_t))` for the experts this call holds, with `cw[t, e]` the
weight the router gave expert e for token t (its probability under top-1
routing, its renormalised share under top-k) and zero for every expert the
token did not choose. One term of the sum is non-zero per token under
top-1 routing, k under top-k; the kernel walks EVERY expert it holds either
way, so a step's time does not depend on where the router sent the tokens
(a decode batch of 64 rows touches nearly all of 16 experts at top-1 and
126 of 128 at top-8 anyway, and a step whose cost moved with the routing
would make two runs with different weights incomparable).

Why a kernel: a decode step is bound by streaming the expert weights
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
             "index by scalar prefetch, every held expert streamed once")


@_workbench_register()
def moe_top1_experts(z, cw, w_gate, w_up, w_down, layer=0, tag="decode"):
    """z [T, H], cw [T, E] (combine weight of token t for held expert e),
    weights `[L, E, H, F]`, `[L, E, H, F]`, `[L, E, F, H]`, `layer` a scalar
    int. Returns float32 [T, H]. Callers gate on `experts_supported`."""
    return _call(z, cw, w_gate, w_up, w_down, jnp.asarray(layer, jnp.int32),
                 str(tag), bool(INTERPRET))


def moe_topk_experts(z, cw, w_gate, w_up, w_down, layer=0, tag="decode"):
    """The same kernel under another name in the trace
    (`moe_topk_experts_<tag>`): a top-k family's call, so that a reader of
    `^moe_top1_experts` keeps finding the top-1 family's calls and nothing
    else."""
    return _call(z, cw, w_gate, w_up, w_down, jnp.asarray(layer, jnp.int32),
                 str(tag), bool(INTERPRET), topk=True)


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
