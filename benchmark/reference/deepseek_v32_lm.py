"""Plain reference for one chip's share of DeepSeek-V3.2-Exp as served
(`configs/deepseek_v32_exp.json`): one teacher-forced causal forward over
prompt + served tokens in float32, `jax.default_matmul_precision("highest")`,
the EXPANDED attention only, no cache pool, no kernel, one sequence at a
time, independent of paddle_tpu (it reads the engine's weights by name and
its configuration's numbers, and nothing else).

One layer, for token t of a sequence (x_t in R^H, float32; `z = RMSNorm(x)`,
eps 1e-6):

 1. queries. c_q = RMSNorm(W_qa z) (q_lora_rank); [q_nope_h | q_rope_h] =
    W_qb c_q for every head (nope + rope lanes); rotary on q_rope_h.
 2. what others read of t. [c | k_r] = W_kva z; c_kv = RMSNorm(c)
    (kv_lora_rank); k_rope = rotary(k_r), one head shared by all.
 3. attention. [k_nope_h | v_h] = W_kvb,h c_kv; s_h(t, s) = (q_nope_h(t) .
    k_nope_h(s) + q_rope_h(t) . k_rope(s)) x (nope + rope)^-0.5 x m^2, m =
    0.1 ln(factor) + 1; softmax over the selected s <= t; x' = x + W_o
    concat_h(sum_s p_h v_h).
 4. indexer. qI_j = W_qI c_q (J heads of D), kI = LayerNorm(W_kI z) with
    gain and bias, rotary on the first `rope` lanes of both, w = W_w z x
    J^-0.5 x D^-0.5; I(t, s) = sum_j w_tj relu(qI_tj . kI_s); a query
    attends the min(k, t + 1) positions of largest I, ties to the lower
    position.
 5. rotary. theta under YaRN (factor, original context, beta_fast,
    beta_slow): a lane pair keeps its frequency below the correction
    dimension of beta_fast, is divided by the factor above that of
    beta_slow, a linear ramp between; cos and sin unscaled. The latent
    attention pairs lanes (2i, 2i + 1), the indexer (i, i + rope/2).
 6. feed-forward, u = RMSNorm(x'). A leading layer: SwiGLU. A routed
    layer: s = sigmoid(W_r u) over all experts; in each of the groups the
    two largest s + b are summed, the best groups kept, the k largest s +
    b inside them chosen; weights scaling x s_e / sum_chosen s; output =
    shared expert(u) + the weighted sum over the chosen experts THIS CHIP
    HOLDS (the first `experts_held`). What the absent experts would add is
    left out, as in the served program, and that partial result goes on.
 7. after the last layer RMSNorm and the untied head over the served
    slice of the vocabulary.

TWO CHOICES ARE TEACHER-FORCED, each with a margin that says how wrong the
engine's choice was by this reference's own float32 lights (the reasons are
`keye_lm`'s: a top-k over random weights flips on rounding, and a wrong
selection moves a random model's logits little):

  * the EXPERTS: the engine reports its k experts (ids among ALL experts)
    for every (position, routed layer); the reference follows them, weighs
    them from its own scores, and reports `route_margin`, the larger of
    two readings on its own `s + b`: how far its best group the engine's
    experts do not lie in sums above the weakest group they force in, and
    how far its best expert outside the followed set (inside those groups)
    lies above the weakest inside. Experts spread over more groups than
    the limit read `MISCOUNT`.
  * the SELECTION, for the positions a marked request computed, as
    `(first, words)` (the engine packs thirty-two PAGES into a word;
    `_unpack`): the reference attends exactly that and reports
    `select_margin`, `max(0, max_{s not in S} I(t, s) - min_{s in S} I(t,
    s))` in standard deviations of that query's live scores; a set that
    does not hold exactly min(k, t + 1) live positions reads `MISCOUNT`.
    The positions of a shared document were computed by ANOTHER marked
    request (the one that prefilled it): its selection is followed there
    (`ahead`). Where none is given the reference selects for itself.

Memory and time. A float32 copy of the weights does not fit beside the
engine, `[33k, 33k]` scores fit nowhere, and at this width neither do the
expanded keys of 33k tokens (128 heads x 256 x 4 B = 131 KB a token). Only
attention crosses positions, and it reads of another token its `c_kv`,
`k_rope` and indexer key (704 float32 a layer), so the sequence is walked
in SEGMENTS of `_SEGMENT` tokens, each through every layer against a cache
of those three for the tokens before it; inside a segment the heads run
`_HEADS` at a time (their keys and values expanded from the cached latents
for that group alone, their weights upcast a group at a time), queries in
blocks of `_QUERY_BLOCK`; the k-th largest score is found a bit at a time;
the dense SwiGLU's weights are upcast `_FFN_COLUMNS` columns at a time, an
expert's one expert at a time; the head is reduced over blocks of the
vocabulary. `check_sequences` computes a shared prefix ONCE (its cache).
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

_ATTENTION_KEYS = (
    "attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
    "wqi", "wki", "ki_norm_w", "ki_norm_b", "ww", "ffn_norm")
_DENSE_KEYS = ("w_gate", "w_up", "w_down")
_MOE_KEYS = ("router_w", "router_bias", "shared_gate", "shared_up",
             "shared_down")
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
_VOCAB_BLOCK = 16384
_QUERY_BLOCK = 128
_HEADS = 8                  # heads whose keys and values exist at once
_SEGMENT = 4096             # tokens walked through the layers together
_KEY_STEP = 8192            # the cache grows in steps: few compiled shapes
_SUFFIX = 768               # what follows a shared prefix pads to this
_FFN_COLUMNS = 2048
_EXPERT_ROWS = 256          # an expert's token count pads to a multiple
MISCOUNT = 1e9              # the margin of a followed set of the wrong size


def read_params(get, cfg, round_to=None) -> dict:
    """The engine's weights AS STORED (no copy, no upcast), by the names
    serving.model gives them: `get(name)` returns an array. `round_to` (a
    dtype name) makes every later upcast go through that dtype first: the
    reading of a precision below the stated one."""
    del cfg
    out = {"word_emb": get("dec.word_emb"), "lm_head": get("dec.lm_head"),
           "final_norm": get("dec.final_norm.scale"), "_round_to": round_to}
    for kind, own in (("dense", _DENSE_KEYS), ("moe", _MOE_KEYS)):
        for k in _ATTENTION_KEYS + own:
            out[f"{kind}.{k}"] = get(f"dec.layers.{kind}.{k}")
    for k in _EXPERT_KEYS:
        out[k] = get("dec.layers." + k)
    return out


def _f32(a, round_to=None):
    a = jnp.asarray(a)
    if round_to is not None and a.dtype != jnp.float32:
        a = a.astype(round_to)      # only what is stored below float32
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """Equation 5: the `dim / 2` inverse frequencies, float32."""
    half = dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)
    if not len(yarn):
        return inv.astype(np.float32)
    factor, original, beta_fast, beta_slow = (float(v) for v in yarn[:4])

    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (inv / factor * ramp + inv * (1.0 - ramp)).astype(np.float32)


def _angles(n, offset, inv):
    pos = (offset + jnp.arange(n, dtype=jnp.int32)).astype(jnp.float32)
    ang = pos[:, None, None] * jnp.asarray(inv)
    return jnp.cos(ang), jnp.sin(ang)


def _rotary_pairs(x, offset, inv):
    """x [T, heads, d]: lanes (2i, 2i + 1) rotate together."""
    cos, sin = _angles(x.shape[0], offset, inv)
    p = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = p[..., 0], p[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _rotary_halves(x, offset, inv, dim):
    """x [T, heads, d]: lanes (i, i + dim/2) of the first `dim` rotate
    together, the rest pass."""
    cos, sin = _angles(x.shape[0], offset, inv)
    half = dim // 2
    a, b = x[..., :half], x[..., half:dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., dim:]], axis=-1)


class Sizes:
    """The configuration's numbers, read once (hashable: a static argument
    of the jitted pieces)."""
    _FIELDS = ("nh", "dn", "dr", "dv", "rq", "rkv", "J", "D", "topk", "k",
               "groups", "kept", "held", "E", "scaling", "theta", "yarn",
               "mscale", "eps", "L", "Ld")

    def __init__(self, cfg):
        self.nh, self.dn = cfg.num_heads, cfg.attn_head_dim
        self.dr, self.dv = cfg.rope_head_dim, cfg.v_head_dim
        self.rq, self.rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        self.J, self.D = cfg.index_heads, cfg.index_head_dim
        self.topk, self.k = cfg.index_topk, cfg.experts_per_token
        self.groups, self.kept = cfg.expert_groups, cfg.groups_per_token
        self.E = cfg.num_experts
        self.held = cfg.experts_held or cfg.num_experts
        self.scaling = float(cfg.routed_scaling)
        self.theta = float(cfg.rope_theta)
        self.yarn = tuple(float(v) for v in cfg.yarn)
        self.mscale = float(cfg.softmax_mscale)
        self.eps = float(cfg.rms_norm_eps)
        self.L, self.Ld = cfg.num_layers, cfg.dense_layers

    def _key(self):
        return tuple(getattr(self, f) for f in self._FIELDS)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return self._key() == other._key()

    @property
    def inv_freq(self):
        return yarn_inv_freq(self.dr, self.theta, self.yarn)


@functools.partial(jax.jit, static_argnames=("sz",))
def _project(x, p, offset, sz):
    """Equations 1, 2 and 4 for the tokens x [n, H] at positions offset ..:
    c_q [n, rq]; what other positions read of them, c_kv [n, rkv], k_rope
    [n, dr], kI [n, D]; the indexer's qI [n, J, D] and w [n, J]."""
    n = x.shape[0]
    inv = sz.inv_freq
    z = _rms_norm(x, p["attn_norm"], sz.eps)
    c_q = _rms_norm(z @ p["wq_a"], p["q_norm"], sz.eps)
    kv = z @ p["wkv_a"]
    c_kv = _rms_norm(kv[:, :sz.rkv], p["kv_norm"], sz.eps)
    k_rope = _rotary_pairs(kv[:, None, sz.rkv:], offset, inv)[:, 0]
    ki = z @ p["wki"]
    mu = jnp.mean(ki, axis=-1, keepdims=True)
    var = jnp.mean((ki - mu) ** 2, axis=-1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(var + sz.eps) * p["ki_norm_w"] \
        + p["ki_norm_b"]
    ki = _rotary_halves(ki[:, None, :], offset, inv, sz.dr)[:, 0]
    qi = _rotary_halves((c_q @ p["wqi"]).reshape(n, sz.J, sz.D), offset,
                        inv, sz.dr)
    w = (z @ p["ww"]) * (sz.J ** -0.5 * sz.D ** -0.5)
    return c_q, c_kv, k_rope, ki, qi, w


@functools.partial(jax.jit, donate_argnums=(0,))
def _store(cache, new, at):
    return tuple(jax.lax.dynamic_update_slice_in_dim(c, v, at, 0)
                 for c, v in zip(cache, new))


def _kth_largest_bits(u, k):
    """u [Q, T] uint32 -> [Q, 1]: its k-th largest value along T, by 32
    counting passes (most significant bit first)."""
    def narrow(i, lo):
        cand = lo | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(u >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, lo)

    return jax.lax.fori_loop(0, 32, narrow,
                             jnp.zeros((u.shape[0], 1), jnp.uint32))


def _own_selection(scores, live, k):
    """Equation 4's selection as a mask [Q, T]."""
    s = jnp.where(live, scores, -jnp.inf)
    bits = jax.lax.bitcast_convert_type(jnp.where(s == 0, 0.0, s), jnp.uint32)
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    kth = _kth_largest_bits(u, k)
    above, ties = u > kth, (u == kth) & live
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    ties = ties & (jnp.cumsum(ties, axis=-1) <= room)
    return live & (above | ties)


def _unpack(words):
    """Selection words [Q, G, ps] uint32 -> bool [Q, G * 32 * ps], position
    by position."""
    bit = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    return ((words[:, :, None, :] >> bit) & 1).astype(bool).reshape(
        words.shape[0], -1)


def _forced(segments: list, layer: int, lo: int, hi: int, shape: tuple):
    """The selection to follow at positions lo .. hi - 1 in `layer`: words
    [hi - lo, G, ps] and which rows have one."""
    words = np.zeros((hi - lo,) + shape, np.uint32)
    use = np.zeros(hi - lo, bool)
    for first, w in segments:
        a, b = max(lo, first), min(hi, first + len(w))
        if a < b:
            words[a - lo:b - lo, :w.shape[2]] = w[a - first:b - first, layer]
            use[a - lo:b - lo] = True
    return words, use


@functools.partial(jax.jit, static_argnames=("k",))
def _select_block(q0, qi, w, forced, use, ki, k):
    """Equation 4 for the queries at positions q0 .. q0 + Q against the
    indexer keys ki [T, D] of every position: the mask they attend under
    [Q, T] (`forced` words where `use`, their own selection elsewhere) and
    the select margin [Q]."""
    Q, T = qi.shape[0], ki.shape[0]
    at = jnp.arange(T, dtype=jnp.int32)[None, :]
    live = at <= q0 + jnp.arange(Q, dtype=jnp.int32)[:, None]

    def add_head(acc, head):                # one indexer head at a time
        qj, wj = head                       # [Q, D], [Q]
        return acc + jax.nn.relu(qj @ ki.T) * wj[:, None], None

    scores, _ = jax.lax.scan(add_head, jnp.zeros((Q, T), jnp.float32),
                             (jnp.moveaxis(qi, 1, 0), w.T))
    keep = _own_selection(scores, live, k)

    def given():                # the followed words as a mask [Q, T]
        bits = _unpack(forced)
        bits = bits[:, :T] if bits.shape[1] >= T else jnp.pad(
            bits, ((0, 0), (0, T - bits.shape[1])))
        return bits & live

    want = jnp.sum(keep, axis=-1)           # min(k, positions that exist)
    keep = jnp.where(use[:, None], jax.lax.cond(
        jnp.any(use), given, lambda: jnp.zeros((Q, T), bool)), keep)
    # every bit counts, also one set past the query or past the keys
    miscount = use & ((jnp.sum(_unpack(forced), axis=-1) != want)
                      | (jnp.sum(keep, axis=-1) != want))
    n = jnp.sum(live, axis=-1)
    mean = jnp.sum(jnp.where(live, scores, 0.0), axis=-1) / n
    std = jnp.sqrt(jnp.sum(jnp.where(live, (scores - mean[:, None]) ** 2,
                                      0.0), axis=-1) / n)
    best_out = jnp.max(jnp.where(live & ~keep, scores, -jnp.inf), axis=-1)
    worst_in = jnp.min(jnp.where(keep, scores, jnp.inf), axis=-1)
    margin = jnp.where(use & jnp.isfinite(best_out),
                       jnp.maximum(best_out - worst_in, 0.0)
                       / jnp.maximum(std, 1e-30), 0.0)
    return keep, jnp.where(miscount, MISCOUNT, margin)


@functools.partial(jax.jit, static_argnames=("sz", "round_to"))
def _attend(c_q, keep, c_all, r_all, wq_b, wkv_b, wo, offset, sz, round_to):
    """Equation 3 for the n queries of a segment (c_q [n, rq], their masks
    keep [n, T]) over every cached position (c_all [T, rkv], r_all [T,
    dr]): `W_o o` [n, H]. Heads run `_HEADS` at a time (their weights, as
    stored, are upcast inside), queries in blocks."""
    n, T = keep.shape
    nh, dn, dr, dv = sz.nh, sz.dn, sz.dr, sz.dv
    hg = min(_HEADS, nh)
    block = min(_QUERY_BLOCK, n)
    scale = (dn + dr) ** -0.5 * sz.mscale ** 2
    inv = sz.inv_freq
    groups = nh // hg
    wq = jnp.moveaxis(wq_b.reshape(sz.rq, groups, hg, dn + dr), 1, 0)
    wkv = jnp.moveaxis(wkv_b.reshape(sz.rkv, groups, hg, dn + dv), 1, 0)
    wo_g = wo.reshape(groups, hg * dv, -1)
    keep_b = keep.reshape(n // block, block, T)

    def group(out, ws):
        wq_g, wkv_g, wo_h = (_f32(a, round_to) for a in ws)
        q = jnp.einsum("nr,rhd->nhd", c_q, wq_g)                # [n, hg, .]
        q_nope = q[..., :dn]
        q_rope = _rotary_pairs(q[..., dn:], offset, inv)
        kv = jnp.einsum("tc,chd->thd", c_all, wkv_g)            # [T, hg, .]
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def queries(args):
            qn, qr, mask = args                     # [block, hg, .], [b, T]
            s = (jnp.einsum("qhd,thd->hqt", qn, k_nope)
                 + jnp.einsum("qhd,td->hqt", qr, r_all)) * scale
            a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,thd->qhd", a, v)

        o = jax.lax.map(queries, (
            q_nope.reshape(n // block, block, hg, dn),
            q_rope.reshape(n // block, block, hg, dr), keep_b))
        return out + o.reshape(n, hg * dv) @ wo_h, None

    out, _ = jax.lax.scan(group, jnp.zeros((n, wo.shape[1]), jnp.float32),
                          (wq, wkv, wo_g))
    return out


@functools.partial(jax.jit, static_argnames=("round_to",),
                   donate_argnums=(0,))
def _swiglu_columns(y, u, wg, wu, wd, round_to):
    """y [n, H] += W_d(silu(W_g u) * (W_u u)) for one block of columns."""
    wg, wu, wd = (_f32(a, round_to) for a in (wg, wu, wd))
    return y + (jax.nn.silu(u @ wg) * (u @ wu)) @ wd


def _swiglu(u, wg, wu, wd, i, round_to):
    """Layer i of the stacked `[L, H, F]`, `[L, H, F]`, `[L, F, H]`, sliced
    out of the stacks a block of columns at a time."""
    y = jnp.zeros_like(u)
    for c0 in range(0, wg.shape[2], _FFN_COLUMNS):
        cols = slice(c0, c0 + _FFN_COLUMNS)
        y = _swiglu_columns(y, u, wg[i, :, cols], wu[i, :, cols],
                            wd[i, cols], round_to)
    return y


@functools.partial(jax.jit, static_argnames=("sz",))
def _router(u, router_w, router_bias, forced, sz):
    """Equation 6's choice. forced [n, k] expert ids (-1: route for
    yourself). Returns the experts followed, their weights and the route
    margin (module docstring)."""
    s = jax.nn.sigmoid(u @ router_w)
    n, E = s.shape
    size = E // sz.groups
    biased = s + router_bias
    best2 = jnp.sum(jax.lax.top_k(biased.reshape(n, sz.groups, size), 2)[0],
                    axis=-1)                                    # [n, groups]
    group_ids = jnp.arange(sz.groups, dtype=jnp.int32)

    def choose(allowed_groups):
        allowed = jnp.repeat(allowed_groups, size, axis=1)
        return jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf),
                             sz.k)[1], allowed

    own_groups = jnp.any(jax.lax.top_k(best2, sz.kept)[1][:, :, None]
                         == group_ids, axis=1)
    own, _ = choose(own_groups)
    given = forced[:, :1] >= 0
    follow = jnp.where(given, forced, own)
    # the groups the followed experts lie in come first, the reference's
    # best fill the limit up
    theirs = jnp.any((follow // size)[:, :, None] == group_ids, axis=1)
    order = jnp.where(theirs, best2 + 4.0, best2)   # best2 < 2 < 4
    eng_groups = jnp.any(jax.lax.top_k(order, sz.kept)[1][:, :, None]
                         == group_ids, axis=1)
    spread = jnp.sum(theirs, axis=1) > sz.kept
    group_margin = jnp.maximum(
        jnp.max(jnp.where(eng_groups, -jnp.inf, best2), axis=1)
        - jnp.min(jnp.where(eng_groups, best2, jnp.inf), axis=1), 0.0)
    allowed = jnp.repeat(eng_groups, size, axis=1)
    inside = jnp.any(follow[:, :, None]
                     == jnp.arange(E, dtype=jnp.int32), axis=1)
    expert_margin = jnp.maximum(
        jnp.max(jnp.where(allowed & ~inside, biased, -jnp.inf), axis=1)
        - jnp.min(jnp.where(inside, biased, jnp.inf), axis=1), 0.0)
    margin = jnp.where(spread, MISCOUNT,
                       jnp.maximum(group_margin, expert_margin))
    sf = jnp.take_along_axis(s, follow, axis=1)
    weights = sz.scaling * sf / jnp.sum(sf, axis=1, keepdims=True)
    return follow, weights, margin


@functools.partial(jax.jit, static_argnames=("round_to",),
                   donate_argnums=(0,))
def _one_expert(y, u, rows, weight, wg, wu, wd, round_to):
    """y [n, H] += weight * expert(u[rows]) at `rows` (weight 0 pads)."""
    wg, wu, wd = (_f32(a, round_to) for a in (wg, wu, wd))
    z = u[rows]
    out = (jax.nn.silu(z @ wg) * (z @ wu) * weight[:, None]) @ wd
    return y.at[rows].add(out)


def _layer_params(params: dict, l: int, sz: Sizes, keys) -> dict:
    kind, i = ("dense", l) if l < sz.Ld else ("moe", l - sz.Ld)
    return {k: params[f"{kind}.{k}"][i] for k in keys}


def _round_up(n: int, step: int) -> int:
    return -(-n // step) * step


def _padded_segment(n: int) -> int:
    """Few distinct lengths: each is a compile of every jitted piece."""
    step = _SEGMENT if n > _SUFFIX else _SUFFIX if n > 128 else 64
    return _round_up(n, step)


class Cache:
    """What the tokens before a position hand to it: their count and,
    layer by layer, (c_kv [cap, rkv], k_rope [cap, dr], kI [cap, D]) in
    float32, `cap` a multiple of the growth step."""

    def __init__(self, n: int, layers: list):
        self.n, self.layers = n, layers

    def grown(self, cap: int) -> "Cache":
        """A cache of at least `cap` rows holding the same tokens (a copy:
        what is written behind `n` never shows in this one)."""
        return Cache(self.n, [tuple(jnp.concatenate([a, jnp.zeros(
            (max(cap - a.shape[0], 0), a.shape[1]), a.dtype)])
            for a in layer) for layer in self.layers])


def empty_cache(sz: Sizes) -> Cache:
    return Cache(0, [tuple(jnp.zeros((0, w), jnp.float32)
                           for w in (sz.rkv, sz.dr, sz.D))
                     for _ in range(sz.L)])


def forward(params: dict, tokens, cfg, routes=None, selection=None,
            cache: "Cache | None" = None, want_x: bool = True):
    """tokens [n] int, the WHOLE sequence; the first `cache.n` of them are
    not recomputed (they must be the tokens the cache was made from).
    routes [m, L_routed, k] int: the experts to follow at positions 0 ..
    m-1 (None: route for yourself). selection: one `(first, words)` or a
    list of them (module docstring; None: select for yourself). Returns (x
    [n - cache.n, H] after the last norm, or None without `want_x`;
    route_margin [n - cache.n, L_routed] and select_margin [n - cache.n,
    L]; followed [n - cache.n]: where a selection was followed; the cache
    with the new tokens in it)."""
    sz = Sizes(cfg)
    rt = params.get("_round_to")
    n0 = cache.n if cache is not None else 0
    n = len(tokens) - n0                    # tokens computed here
    Le = sz.L - sz.Ld
    forced_e = np.full((n, Le, sz.k), -1, np.int32)
    if routes is not None:
        m = min(n, len(routes) - n0)
        forced_e[:m] = np.asarray(routes)[n0:n0 + m].reshape(m, Le, sz.k)
    segments = [] if selection is None else [selection] \
        if isinstance(selection, tuple) else list(selection)
    segments = [(first, w[:n0 + n - first]) for first, w in segments]
    words_shape = (max((w.shape[2] for _, w in segments), default=1),
                   segments[0][1].shape[3] if segments else 1)
    # every segment but the last is whole; the last pads to few lengths
    padded = n // _SEGMENT * _SEGMENT + (
        _padded_segment(n % _SEGMENT) if n % _SEGMENT else 0)
    step = _KEY_STEP if n0 + padded > _KEY_STEP else _SUFFIX \
        if n0 + padded > 128 else 64
    cache = (cache or empty_cache(sz)).grown(_round_up(n0 + padded, step))
    layers = list(cache.layers)
    xs, route_margins, select_margins, followed = [], [], [], []
    with jax.default_matmul_precision("highest"):
        for s0 in range(0, n, _SEGMENT):
            m = min(_SEGMENT, n - s0)               # real tokens
            T = _padded_segment(m)
            block = min(_QUERY_BLOCK, T)
            first = n0 + s0                         # position of row 0
            tok = np.zeros(T, np.int32)
            tok[:m] = tokens[first:first + m]
            x = _f32(params["word_emb"][jnp.asarray(tok)], rt)
            r_seg, s_seg = [], []
            followed.append(_forced(segments, 0, first, first + T,
                                    words_shape)[1][:m])
            for l in range(sz.L):
                p = {k: _f32(v, rt) for k, v in _layer_params(
                    params, l, sz, (
                        "attn_norm", "wq_a", "q_norm", "wkv_a", "kv_norm",
                        "wqi", "wki", "ki_norm_w", "ki_norm_b", "ww",
                        "ffn_norm")).items()}
                stored = _layer_params(params, l, sz,
                                       ("wq_b", "wkv_b", "wo"))
                c_q, c_kv, k_rope, ki, qi, w = _project(
                    x, p, jnp.int32(first), sz=sz)
                # the keys this segment sees: the cache up to its last row
                seen = min(_round_up(first + T, step),
                           layers[l][0].shape[0])
                layers[l] = _store(layers[l], (c_kv, k_rope, ki),
                                   jnp.int32(first))
                c_all, r_all, ki_all = (a[:seen] for a in layers[l])
                keeps, margins = [], []
                for q0 in range(0, T, block):
                    rows = slice(q0, q0 + block)
                    words, use = _forced(segments, l, first + q0,
                                         first + q0 + block, words_shape)
                    keep, mg = _select_block(
                        jnp.int32(first + q0), qi[rows], w[rows],
                        jnp.asarray(words), jnp.asarray(use), ki_all,
                        k=min(sz.topk, seen))
                    keeps.append(keep)
                    margins.append(mg)
                o = _attend(c_q, jnp.concatenate(keeps), c_all, r_all,
                            stored["wq_b"], stored["wkv_b"], stored["wo"],
                            jnp.int32(first), sz=sz, round_to=rt)
                del keeps
                x = x + o
                u = _rms_norm(x, p["ffn_norm"], sz.eps)
                s_seg.append(jnp.concatenate(margins)[:m])
                if l < sz.Ld:
                    x = x + _swiglu(u, *(params[f"dense.{k}"]
                                         for k in _DENSE_KEYS), l, rt)
                    continue
                i = l - sz.Ld
                follow, weights, r_margin = _router(
                    u, _f32(params["moe.router_w"][i]),
                    _f32(params["moe.router_bias"][i]),
                    jnp.asarray(np.pad(forced_e[s0:s0 + m, i],
                                       ((0, T - m), (0, 0)),
                                       constant_values=-1)), sz=sz)
                follow, weights = np.asarray(follow), np.asarray(weights)
                y = _swiglu(u, *(params[f"moe.shared_{k}"]
                                 for k in ("gate", "up", "down")), i, rt)
                for e in range(sz.held):    # the absent experts add nothing
                    rows, slot = np.nonzero(follow[:m] == e)
                    if not len(rows):
                        continue
                    pad = _round_up(len(rows), _EXPERT_ROWS) - len(rows)
                    y = _one_expert(
                        y, u, jnp.asarray(np.pad(rows, (0, pad))),
                        jnp.asarray(np.pad(weights[rows, slot], (0, pad))),
                        *(params[key][i, e] for key in _EXPERT_KEYS),
                        round_to=rt)
                x = x + y
                r_seg.append(np.asarray(r_margin)[:m])
            route_margins.append(np.stack(r_seg, -1))
            select_margins.append(np.asarray(jnp.stack(s_seg, -1)))
            if want_x:
                xs.append(_rms_norm(x, _f32(params["final_norm"], rt),
                                    sz.eps)[:m])
    return (jnp.concatenate(xs) if want_x else None,
            np.concatenate(route_margins), np.concatenate(select_margins),
            np.concatenate(followed), Cache(n0 + n, layers))


@jax.jit
def _block_logits(x, head_block):
    return x @ head_block


def logit_gaps(params: dict, x, tokens) -> np.ndarray:
    """x [M, H] final-norm states, tokens [M] the tokens served after them:
    per row, the best logit minus the served token's, reduced over blocks
    of the vocabulary (the head is `[H, V]`, untied)."""
    head = params["lm_head"]
    V = head.shape[1]
    tokens = np.asarray(tokens)
    best = np.full(len(tokens), -np.inf, np.float32)
    own = np.zeros(len(tokens), np.float32)
    with jax.default_matmul_precision("highest"):
        for v0 in range(0, V, _VOCAB_BLOCK):
            lg = np.asarray(_block_logits(
                x, _f32(head[:, v0:v0 + _VOCAB_BLOCK],
                        params.get("_round_to"))))
            best = np.maximum(best, lg.max(axis=1))
            t = tokens - v0
            here = (t >= 0) & (t < lg.shape[1])
            own[here] = lg[np.flatnonzero(here), t[here]]
    return best - own


def logits(params: dict, tokens, cfg, routes=None,
           selection=None) -> np.ndarray:
    """The full forward's logits [n, V] for a short sequence (tests); what
    is not given to follow the reference chooses itself."""
    x = forward(params, list(tokens), cfg, routes, selection)[0]
    with jax.default_matmul_precision("highest"):
        return np.asarray(x @ _f32(params["lm_head"],
                                   params.get("_round_to")))


def _margins(route, select, followed) -> dict:
    """What a stretch of positions adds to a sequence's readings: route
    margins [n, L_routed], select margins [n, L], followed [n]."""
    judged = followed if followed.any() else np.ones(len(followed), bool)
    return {"route_margin": float(route[judged].max(initial=0.0)),
            "route_margin_unfollowed": float(route[~judged].max(initial=0.0)),
            "select_margin_by_layer": select[judged].max(axis=0).tolist()}


def check_sequences(params: dict, sequences: list, cfg,
                    budget_s: float | None = None, at_least: int = 0) -> list:
    """For each (prompt, served, routes, selection[, ahead]) — routes [>=
    len(prompt) + len(served) - 1, L_routed, k] the engine's experts by
    position or None; selection `(first, words)` or None; ahead, words for
    positions 0 .. first - 1 (the selection of the request that computed a
    shared prefix) or None — a dict: `gap`, the largest amount by which a
    served token's logit sits below the best logit at its position with
    the engine's choices followed; `route_margin` and `select_margin`, the
    largest over the positions whose selection was followed (every
    position where none was) and over the layers, with
    `select_margin_by_layer`; and, not judged, `route_margin_unfollowed`.
    Sequences that share the tokens, experts and `ahead` before their
    selection (whole segments' worth of them) share that part's forward;
    give them one after the other. With `budget_s` the list may come back
    shorter: once `at_least` are graded, a sequence is not started if its
    forward (at the pace of the last prefix and the last suffix) would end
    past the budget."""
    out, shared = [], (None, None)
    t0, prefix_s, suffix_s = time.perf_counter(), 0.0, 0.0
    for sequence in sequences:
        prompt, served, routes, selection, ahead = \
            (tuple(sequence) + (None,) * 3)[:5]
        seq = (list(prompt) + list(served))[:-1]
        first = selection[0] if selection is not None else 0
        segments = [(0, ahead)] * (ahead is not None) \
            + [selection] * (selection is not None)
        n0 = first // _QUERY_BLOCK * _QUERY_BLOCK if first > _SEGMENT // 2 \
            else 0
        key = (tuple(seq[:n0]), None if routes is None
               else np.asarray(routes)[:n0].tobytes(), id(ahead)) \
            if n0 else None
        ahead_s = suffix_s + (prefix_s if n0 and shared[0] != key else 0.0)
        if budget_s is not None and len(out) >= at_least \
                and time.perf_counter() - t0 + ahead_s > budget_s:
            break
        parts, cache = [], None
        if n0:
            if shared[0] != key:
                shared, t1 = (None, None), time.perf_counter()  # drop first
                _, r, sm, f, made = forward(params, seq[:n0], cfg, routes,
                                            segments, want_x=False)
                shared = (key, (made, _margins(r, sm, f)))
                prefix_s = time.perf_counter() - t1
            cache, before = shared[1]
            parts.append(before)
        t1 = time.perf_counter()
        x, r, sm, f, _ = forward(params, seq, cfg, routes, segments, cache)
        parts.append(_margins(r, sm, f))
        # served tokens pad to one row count: one compile of the head
        at = len(prompt) - 1 - n0 + np.arange(len(served))
        rows = _round_up(len(served), 64)
        xs = jnp.pad(x[at], ((0, rows - len(served)), (0, 0)))
        gaps = logit_gaps(params, xs, list(served)
                          + [0] * (rows - len(served)))[:len(served)]
        suffix_s = time.perf_counter() - t1
        by_layer = np.max([m["select_margin_by_layer"] for m in parts], 0)
        out.append({
            "gap": float(gaps.max()),
            "route_margin": max(m["route_margin"] for m in parts),
            "select_margin": float(by_layer.max()),
            "select_margin_by_layer": by_layer.tolist(),
            "route_margin_unfollowed": max(m["route_margin_unfollowed"]
                                           for m in parts)})
    return out


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """`decoder_lm.worst_logit_gaps` for (prompt, served[, routes[,
    selection]]) tuples; what is missing the reference chooses itself."""
    return [c["gap"] for c in check_sequences(params, sequences, cfg)]
