"""Plain reference for the first pipeline stage of Xing4.0-29B-A4B as served
(`configs/xing4_29b_a4b.json`): one teacher-forced causal forward over
prompt + served tokens in float32, `jax.default_matmul_precision("highest")`,
the EXPANDED attention only, every position attending EVERY position before
it (the configuration has no indexer), no cache pool, no kernel, one sequence
at a time, independent of paddle_tpu (it reads the engine's weights by name
and its configuration's numbers, and nothing else).

THE RESIDUAL PATH (mHC, arXiv:2512.24880 section 4, on Hyper-Connections,
arXiv:2409.19606). A token's residual X is `[n, C]`: n = hc_mult streams of
C = hidden_size. Around every sub-layer F (the attention, the feed-forward;
each keeps its own RMSNorm), with that sub-layer's own P [n C, n (n + 2)]
(columns: pre, post, res row-major), a [3], b [n (n + 2)]:

    x'     = vec(X) * rsqrt(mean(vec(X)^2) + hc_eps)        no gain
    H_pre  = sigmoid(a_pre  * (x' P_pre)  + b_pre)          [n]
    H_post = 2 * sigmoid(a_post * (x' P_post) + b_post)     [n]
    M      = exp(clip(a_res * mat(x' P_res) + b_res, lo, hi))      [n, n]
    hc_sinkhorn_iters times: M <- M / (colsum(M) + hc_eps);
                             M <- M / (rowsum(M) + hc_eps);  H_res = M
    u      = sum_i H_pre[i] X[i]                            F's input
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] * F(u)

X_0 is the embedding in every stream; the final RMSNorm and the head read
the SUM of the streams.

ONE LAYER, for token t (u as above; `z = RMSNorm(u)`, eps rms_norm_eps):

 1. queries. c_q = RMSNorm(W_qa z) (q_lora_rank); [q_nope_h | q_rope_h] =
    W_qb c_q for every head; rotary on q_rope_h.
 2. what others read of t. [c | k_r] = W_kva z; c_kv = RMSNorm(c)
    (kv_lora_rank); k_rope = rotary(k_r), one head shared by all.
 3. attention. [k_nope_h | v_h] = W_kvb,h c_kv; s_h(t, s) = (q_nope_h(t) .
    k_nope_h(s) + q_rope_h(t) . k_rope(s)) x (nope + rope)^-0.5 x m^2, m =
    0.1 ln(factor) + 1; softmax over ALL s <= t; F = W_o concat_h(sum_s p_h
    v_h).
 4. rotary. theta under YaRN (factor, original context, beta_fast,
    beta_slow): a lane pair keeps its frequency below the correction
    dimension of beta_fast, is divided by the factor above that of
    beta_slow, a linear ramp between; cos and sin unscaled; lanes pair (2i,
    2i + 1).
 5. feed-forward, on its own mix u' with z' = RMSNorm(u'). A leading
    layer: SwiGLU. A routed layer: s = sigmoid(W_r z') over all experts; in
    each of the groups the two largest s + b are summed, the best groups
    kept, the k largest s + b inside them chosen; weights scaling x s_e /
    sum_chosen s; F = shared expert(z') + the weighted sum over the chosen
    experts the engine HOLDS (all of them as served).
 6. after the last layer RMSNorm of the streams' sum and the untied head.

THE EXPERTS ARE TEACHER-FORCED: the engine reports its k experts for every
(position, routed layer); the reference follows them, weighs them from its
own scores, and reports `route_margin`: how far, on its own `s + b`, its best
expert outside the followed set lies above the weakest inside (and the
groups' reading of the same; experts spread over more groups than the limit
read `MISCOUNT`). A top-k over random weights flips on rounding, and a
tolerance wide enough for a wrong expert absorbs everything.

Memory and time. A float32 copy of the weights does not fit beside the
engine and `[33k, 33k]` scores fit nowhere. Only attention crosses
positions, and it reads of another token its `c_kv` and `k_rope` (576
float32 a layer), so the sequence is walked in SEGMENTS of `_SEGMENT`
tokens, each through every layer against a cache of those two for the tokens
before it; inside a segment the heads run `_HEADS` at a time (keys and values
expanded from the cached latents for that group alone, weights upcast a
group at a time), queries in blocks of `_QUERY_BLOCK`; the dense SwiGLU's
weights are upcast `_FFN_COLUMNS` columns at a time, an expert's one expert
at a time; the head is reduced over blocks of the vocabulary.
`check_sequences` computes a shared prefix ONCE (its cache), whatever the
order the sequences come in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_ATTENTION_KEYS = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                   "wkv_b", "wo", "ffn_norm", "hc_w", "hc_a", "hc_b")
_DENSE_KEYS = ("w_gate", "w_up", "w_down")
_MOE_KEYS = ("router_w", "router_bias", "shared_gate", "shared_up",
             "shared_down")
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
_VOCAB_BLOCK = 8192
_QUERY_BLOCK = 128
_HEADS = 4                  # heads whose keys and values exist at once
_SEGMENT = 2048             # tokens walked through the layers together
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
    """Equation 4: the `dim / 2` inverse frequencies, float32."""
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


def _rotary_pairs(x, offset, inv):
    """x [T, heads, d] at positions offset ..: lanes (2i, 2i + 1) rotate
    together."""
    pos = (offset + jnp.arange(x.shape[0], dtype=jnp.int32)).astype(
        jnp.float32)
    ang = pos[:, None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    p = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = p[..., 0], p[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


class Sizes:
    """The configuration's numbers, read once (hashable: a static argument
    of the jitted pieces)."""
    _FIELDS = ("nh", "dn", "dr", "dv", "rq", "rkv", "k", "groups", "kept",
               "held", "E", "scaling", "theta", "yarn", "mscale", "eps", "L",
               "Ld", "n", "iters", "hc_eps", "clamp")

    def __init__(self, cfg):
        self.nh, self.dn = cfg.num_heads, cfg.attn_head_dim
        self.dr, self.dv = cfg.rope_head_dim, cfg.v_head_dim
        self.rq, self.rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        self.k = cfg.experts_per_token
        self.groups, self.kept = cfg.expert_groups, cfg.groups_per_token
        self.E = cfg.num_experts
        self.held = cfg.experts_held or cfg.num_experts
        self.scaling = float(cfg.routed_scaling)
        self.theta = float(cfg.rope_theta)
        self.yarn = tuple(float(v) for v in cfg.yarn)
        self.mscale = float(cfg.softmax_mscale)
        self.eps = float(cfg.rms_norm_eps)
        self.L, self.Ld = cfg.num_layers, cfg.dense_layers
        self.n, self.iters = cfg.hc_mult, cfg.hc_sinkhorn_iters
        self.hc_eps = float(cfg.hc_eps)
        self.clamp = tuple(float(v) for v in cfg.hc_res_clamp)

    def _key(self):
        return tuple(getattr(self, f) for f in self._FIELDS)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return self._key() == other._key()

    @property
    def inv_freq(self):
        return yarn_inv_freq(self.dr, self.theta, self.yarn)


# -- the residual path --------------------------------------------------------


def sinkhorn(logits, iters: int, eps: float):
    """logits [T, n, n] (row i, column j) -> exp(.) normalised `iters`
    times, columns then rows."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # a column's sum
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)   # a row's sum
    return m


def mappings(X, w, a, b, sz: Sizes):
    """X [T, n, C] -> H_pre [T, n], H_post [T, n], H_res [T, n, n]."""
    T, n, _ = X.shape
    flat = X.reshape(T, -1)
    xp = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                              + sz.hc_eps)
    raw = xp @ w
    pre = jax.nn.sigmoid(a[0] * raw[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * raw[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
    return pre, post, sinkhorn(jnp.clip(res, *sz.clamp), sz.iters, sz.hc_eps)


@functools.partial(jax.jit, static_argnames=("sz",))
def _mix_in(X, w, a, b, sz):
    """The mappings of one sub-layer and its input u [T, C]."""
    pre, post, res = mappings(X, w, a, b, sz)
    return jnp.einsum("ti,tic->tc", pre, X), post, res


@functools.partial(jax.jit, donate_argnums=(0,))
def _mix_out(X, res, post, f):
    return jnp.einsum("tij,tjc->tic", res, X) + post[:, :, None] * f[:, None]


# -- attention ----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("sz",))
def _project(u, p, offset, sz):
    """Equations 1 and 2 for the tokens u [n, H] at positions offset ..: c_q
    [n, rq]; what other positions read of them, c_kv [n, rkv] and k_rope [n,
    dr]."""
    z = _rms_norm(u, p["attn_norm"], sz.eps)
    c_q = _rms_norm(z @ p["wq_a"], p["q_norm"], sz.eps)
    kv = z @ p["wkv_a"]
    c_kv = _rms_norm(kv[:, :sz.rkv], p["kv_norm"], sz.eps)
    k_rope = _rotary_pairs(kv[:, None, sz.rkv:], offset, sz.inv_freq)[:, 0]
    return c_q, c_kv, k_rope


@functools.partial(jax.jit, donate_argnums=(0,))
def _store(cache, new, at):
    return tuple(jax.lax.dynamic_update_slice_in_dim(c, v, at, 0)
                 for c, v in zip(cache, new))


@functools.partial(jax.jit, static_argnames=("sz", "round_to"))
def _attend(c_q, c_all, r_all, wq_b, wkv_b, wo, offset, sz, round_to):
    """Equation 3 for the n queries of a segment at positions offset .. (c_q
    [n, rq]) over every cached position (c_all [T, rkv], r_all [T, dr], row s
    the token at position s): `W_o o` [n, H]. Heads run `_HEADS` at a time
    (their weights, as stored, are upcast inside), queries in blocks."""
    n, T = c_q.shape[0], c_all.shape[0]
    nh, dn, dr, dv = sz.nh, sz.dn, sz.dr, sz.dv
    hg = min(_HEADS, nh)
    block = min(_QUERY_BLOCK, n)
    scale = (dn + dr) ** -0.5 * sz.mscale ** 2
    inv = sz.inv_freq
    groups = nh // hg
    wq = jnp.moveaxis(wq_b.reshape(sz.rq, groups, hg, dn + dr), 1, 0)
    wkv = jnp.moveaxis(wkv_b.reshape(sz.rkv, groups, hg, dn + dv), 1, 0)
    wo_g = wo.reshape(groups, hg * dv, -1)
    at = jnp.arange(T, dtype=jnp.int32)[None, :]
    q_pos = (offset + jnp.arange(n, dtype=jnp.int32)).reshape(
        n // block, block)

    def group(out, ws):
        wq_g, wkv_g, wo_h = (_f32(a, round_to) for a in ws)
        q = jnp.einsum("nr,rhd->nhd", c_q, wq_g)                # [n, hg, .]
        q_nope = q[..., :dn]
        q_rope = _rotary_pairs(q[..., dn:], offset, inv)
        kv = jnp.einsum("tc,chd->thd", c_all, wkv_g)            # [T, hg, .]
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def queries(args):
            qn, qr, pos = args                      # [block, hg, .], [block]
            s = (jnp.einsum("qhd,thd->hqt", qn, k_nope)
                 + jnp.einsum("qhd,td->hqt", qr, r_all)) * scale
            live = at <= pos[:, None]                           # [block, T]
            a = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,thd->qhd", a, v)

        o = jax.lax.map(queries, (
            q_nope.reshape(n // block, block, hg, dn),
            q_rope.reshape(n // block, block, hg, dr), q_pos))
        return out + o.reshape(n, hg * dv) @ wo_h, None

    out, _ = jax.lax.scan(group, jnp.zeros((n, wo.shape[1]), jnp.float32),
                          (wq, wkv, wo_g))
    return out


# -- feed-forward -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("round_to",),
                   donate_argnums=(0,))
def _swiglu_columns(y, u, wg, wu, wd, round_to):
    """y [n, H] += W_d(silu(W_g u) * (W_u u)) for one block of columns."""
    wg, wu, wd = (_f32(a, round_to) for a in (wg, wu, wd))
    return y + (jax.nn.silu(u @ wg) * (u @ wu)) @ wd


def _swiglu(u, wg, wu, wd, i, round_to):
    """Layer i of the stacked `[L, H, F]`, `[L, H, F]`, `[L, F, H]`, sliced
    out of the stacks a block of columns at a time (`i` an int32 scalar:
    one slice program for every layer)."""
    y = jnp.zeros_like(u)
    for c0 in range(0, wg.shape[2], _FFN_COLUMNS):
        cols = slice(c0, c0 + _FFN_COLUMNS)
        y = _swiglu_columns(y, u, wg[i, :, cols], wu[i, :, cols],
                            wd[i, cols], round_to)
    return y


@functools.partial(jax.jit, static_argnames=("sz",))
def _router(u, router_w, router_bias, forced, sz):
    """Equation 5's choice. forced [n, k] expert ids (-1: route for
    yourself). Returns the experts followed, their weights and the route
    margin (module docstring)."""
    s = jax.nn.sigmoid(u @ router_w)
    n, E = s.shape
    size = E // sz.groups
    biased = s + router_bias
    best2 = jnp.sum(jax.lax.top_k(biased.reshape(n, sz.groups, size), 2)[0],
                    axis=-1)                                    # [n, groups]
    group_ids = jnp.arange(sz.groups, dtype=jnp.int32)
    own_groups = jnp.any(jax.lax.top_k(best2, sz.kept)[1][:, :, None]
                         == group_ids, axis=1)
    own = jax.lax.top_k(jnp.where(jnp.repeat(own_groups, size, axis=1),
                                  biased, -jnp.inf), sz.k)[1]
    follow = jnp.where(forced[:, :1] >= 0, forced, own)
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
    margin = jnp.where(spread | (jnp.sum(inside, axis=1) != sz.k), MISCOUNT,
                       jnp.maximum(group_margin, expert_margin))
    sf = jnp.take_along_axis(s, follow, axis=1)
    weights = sz.scaling * sf / jnp.sum(sf, axis=1, keepdims=True)
    return follow, weights, margin


@functools.partial(jax.jit, static_argnames=("round_to",),
                   donate_argnums=(0,))
def _one_expert(y, u, rows, weight, wg, wu, wd, i, e, round_to):
    """y [n, H] += weight * expert e of layer i (u[rows]) at `rows` (weight
    0 pads); the stacks `[L, E, ...]` are indexed inside."""
    wg, wu, wd = (_f32(a[i, e], round_to) for a in (wg, wu, wd))
    z = u[rows]
    out = (jax.nn.silu(z @ wg) * (z @ wu) * weight[:, None]) @ wd
    return y.at[rows].add(out)


def _round_up(n: int, step: int) -> int:
    return -(-n // step) * step


def _padded_segment(n: int) -> int:
    """Few distinct lengths: each is a compile of every jitted piece."""
    step = _SEGMENT if n > _SUFFIX else _SUFFIX if n > 128 else 64
    return _round_up(n, step)


class Cache:
    """What the tokens before a position hand to it: their count and, layer
    by layer, (c_kv [cap, rkv], k_rope [cap, dr]) in float32, `cap` a
    multiple of the growth step."""

    def __init__(self, n: int, layers: list):
        self.n, self.layers = n, layers

    def grown(self, cap: int) -> "Cache":
        """A cache of at least `cap` rows holding the same tokens: this one
        where it is large enough (whoever then writes behind `n` writes into
        ITS arrays, which are donated: `forward` hands the arrays back and
        the caller keeps those), a copy with room otherwise."""
        if all(a.shape[0] >= cap for layer in self.layers for a in layer):
            return self
        return Cache(self.n, [tuple(jnp.concatenate([a, jnp.zeros(
            (max(cap - a.shape[0], 0), a.shape[1]), a.dtype)])
            for a in layer) for layer in self.layers])


def empty_cache(sz: Sizes) -> Cache:
    return Cache(0, [tuple(jnp.zeros((0, w), jnp.float32)
                           for w in (sz.rkv, sz.dr)) for _ in range(sz.L)])


def forward(params: dict, tokens, cfg, routes=None,
            cache: "Cache | None" = None, want_x: bool = True,
            room: int = 0):
    """tokens [n] int, the WHOLE sequence; the first `cache.n` of them are
    not recomputed (they must be the tokens the cache was made from; the
    cache's arrays are USED UP: keep the returned one). routes [m, L_routed,
    k] int: the experts to follow at positions 0 .. m-1 (None: route for
    yourself). `room`: rows the returned cache holds beyond the sequence.
    Returns (x [n - cache.n, H], the streams' sum after the last norm, or
    None without `want_x`; route_margin [n - cache.n, L_routed]; the cache
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
    # every segment but the last is whole; the last pads to few lengths
    padded = n // _SEGMENT * _SEGMENT + (
        _padded_segment(n % _SEGMENT) if n % _SEGMENT else 0)
    step = _KEY_STEP if n0 + padded + room > _KEY_STEP else _SUFFIX \
        if n0 + padded + room > 128 else 64
    cache = (cache or empty_cache(sz)).grown(
        _round_up(n0 + padded + room, step))
    layers = list(cache.layers)
    small = ("attn_norm", "wq_a", "q_norm", "wkv_a", "kv_norm", "ffn_norm",
             "hc_w", "hc_a", "hc_b")
    xs, route_margins = [], []
    with jax.default_matmul_precision("highest"):
        for s0 in range(0, n, _SEGMENT):
            m = min(_SEGMENT, n - s0)               # real tokens
            T = _padded_segment(m)
            first = n0 + s0                         # position of row 0
            tok = np.zeros(T, np.int32)
            tok[:m] = tokens[first:first + m]
            x = _f32(params["word_emb"][jnp.asarray(tok)], rt)
            X = jnp.broadcast_to(x[:, None, :], (T, sz.n, x.shape[1]))
            r_seg = []
            for l in range(sz.L):
                kind, li = ("dense", l) if l < sz.Ld else ("moe", l - sz.Ld)
                i = jnp.int32(li)       # one slice program for every layer
                p = {k: _f32(params[f"{kind}.{k}"][i], rt) for k in small}
                stored = {k: params[f"{kind}.{k}"][i]
                          for k in ("wq_b", "wkv_b", "wo")}
                u, post, res = _mix_in(X, p["hc_w"][0], p["hc_a"][0],
                                       p["hc_b"][0], sz=sz)
                c_q, c_kv, k_rope = _project(u, p, jnp.int32(first), sz=sz)
                # the keys this segment sees: the cache up to its last row
                seen = min(_round_up(first + T, step),
                           layers[l][0].shape[0])
                layers[l] = _store(layers[l], (c_kv, k_rope),
                                   jnp.int32(first))
                c_all, r_all = (a[:seen] for a in layers[l])
                f = _attend(c_q, c_all, r_all, stored["wq_b"],
                            stored["wkv_b"], stored["wo"], jnp.int32(first),
                            sz=sz, round_to=rt)
                X = _mix_out(X, res, post, f)
                u, post, res = _mix_in(X, p["hc_w"][1], p["hc_a"][1],
                                       p["hc_b"][1], sz=sz)
                z = _rms_norm(u, p["ffn_norm"], sz.eps)
                if l < sz.Ld:
                    f = _swiglu(z, *(params[f"dense.{k}"]
                                     for k in _DENSE_KEYS), i, rt)
                    X = _mix_out(X, res, post, f)
                    continue
                follow, weights, r_margin = _router(
                    z, _f32(params["moe.router_w"][i]),
                    _f32(params["moe.router_bias"][i]),
                    jnp.asarray(np.pad(forced_e[s0:s0 + m, li],
                                       ((0, T - m), (0, 0)),
                                       constant_values=-1)), sz=sz)
                follow, weights = np.asarray(follow), np.asarray(weights)
                f = _swiglu(z, *(params[f"moe.shared_{k}"]
                                 for k in ("gate", "up", "down")), i, rt)
                for e in range(sz.held):    # absent experts add nothing
                    rows, slot = np.nonzero(follow[:m] == e)
                    if not len(rows):
                        continue
                    pad = _round_up(len(rows), _EXPERT_ROWS) - len(rows)
                    f = _one_expert(
                        f, z, jnp.asarray(np.pad(rows, (0, pad))),
                        jnp.asarray(np.pad(weights[rows, slot], (0, pad))),
                        *(params[key] for key in _EXPERT_KEYS), i,
                        jnp.int32(e), round_to=rt)
                X = _mix_out(X, res, post, f)
                r_seg.append(np.asarray(r_margin)[:m])
            route_margins.append(np.stack(r_seg, -1))
            if want_x:
                xs.append(_rms_norm(jnp.sum(X, axis=1),
                                    _f32(params["final_norm"], rt),
                                    sz.eps)[:m])
    return (jnp.concatenate(xs) if want_x else None,
            np.concatenate(route_margins), Cache(n0 + n, layers))


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


def logits(params: dict, tokens, cfg, routes=None) -> np.ndarray:
    """The full forward's logits [n, V] for a short sequence (tests); where
    no experts are given to follow the reference routes for itself."""
    x = forward(params, list(tokens), cfg, routes)[0]
    with jax.default_matmul_precision("highest"):
        return np.asarray(x @ _f32(params["lm_head"],
                                   params.get("_round_to")))


def _shared_tokens(prompt) -> int:
    """How many leading tokens of a prompt are computed as a prefix other
    sequences may share: its whole segments, where there are at least
    two."""
    whole = (len(prompt) - 1) // _SEGMENT * _SEGMENT
    return whole if whole > 2 * _SEGMENT else 0


def check_sequences(params: dict, sequences: list, cfg) -> list:
    """For each (prompt, served, routes) — routes [>= len(prompt) +
    len(served) - 1, L_routed, k] the engine's experts by position, or None
    — a dict: `gap`, the largest amount by which a served token's logit
    sits below the best logit at its position with the engine's experts
    followed, and `route_margin`, the largest margin by which the reference
    would have routed a position of the sequence otherwise. Sequences that
    share the tokens and experts of their prompt's whole segments (a cached
    document) share that part's forward, in whatever order they come: its
    cache is made once with room for a suffix, and every sequence writes its
    own suffix behind the document's rows (all `_SUFFIX` padded rows of a
    layer before that layer reads any, so nothing of the sequence before
    shows)."""
    def prefix_key(sequence):
        prompt, _, routes = sequence
        n0 = _shared_tokens(prompt)
        return (n0, tuple(prompt[:n0]), None if routes is None
                else np.asarray(routes)[:n0].tobytes()) if n0 else (0,)

    keys = [prefix_key(s) for s in sequences]
    order = sorted(range(len(sequences)), key=lambda j: (keys[j][0], hash(
        keys[j])))
    out, shared = [None] * len(sequences), (None, None)
    for j in order:
        prompt, served, routes = sequences[j]
        seq = (list(prompt) + list(served))[:-1]
        n0, cache, before = keys[j][0], None, 0.0
        if n0:
            if shared[0] != keys[j]:
                shared = (None, None)           # drop the last one first
                _, r, made = forward(params, seq[:n0], cfg, routes,
                                     want_x=False, room=_SUFFIX)
                shared = (keys[j], (made, float(r.max(initial=0.0))))
            cache, before = shared[1]
        x, r, used = forward(params, seq, cfg, routes, cache)
        if n0:      # the document's rows, in the arrays handed back
            shared = (keys[j], (Cache(n0, used.layers), before))
        # served tokens pad to one row count: one compile of the head
        at = len(prompt) - 1 - n0 + np.arange(len(served))
        rows = _round_up(len(served), 64)
        xs = jnp.pad(x[at], ((0, rows - len(served)), (0, 0)))
        gaps = logit_gaps(params, xs, list(served)
                          + [0] * (rows - len(served)))[:len(served)]
        out[j] = {"gap": float(gaps.max()),
                  "route_margin": max(before, float(r.max(initial=0.0)))}
    return out


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """`decoder_lm.worst_logit_gaps` for (prompt, served[, routes]) tuples;
    without routes the reference routes for itself."""
    full = [(s[0], s[1], s[2] if len(s) > 2 else None) for s in sequences]
    return [c["gap"] for c in check_sequences(params, full, cfg)]
