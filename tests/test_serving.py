"""Serving runtime tests (ISSUE 7): paged KV cache, ragged paged decode
attention (XLA reference + Pallas interpret kernel) equivalence against
dense attention, continuous-batching scheduling (backpressure, preemption,
abort reclamation), and the compile-once-per-bucket contract."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import tuning, unique_name
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.ops import attention_ops as ao
from paddle_tpu.serving import (PagedKVPool, PrefixCache, ServingEngine,
                                build_full_forward_program, decoder_tiny)
from paddle_tpu.serving import model as sv_model
from paddle_tpu.serving.kv_cache import OwnedPoolView, pool_shape
from serving_helpers import preempting


def _rand(shape, seed):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def _scattered_pool(lens, ps, nh, dh, num_pages, seed=0):
    """Contiguous per-row K/V plus its paged scatter: returns
    (k_dense, v_dense, k_pool, v_pool, page_table)."""
    import jax.numpy as jnp

    B = len(lens)
    S = max(lens)
    P = max(-(-l // ps) for l in lens)
    k = _rand((B, nh, S, dh), seed)
    v = _rand((B, nh, S, dh), seed + 1)
    rng = np.random.default_rng(seed + 2)
    perm = iter(rng.permutation(num_pages))
    pt_ = np.zeros((B, P), np.int32)
    for b in range(B):
        for p in range(-(-lens[b] // ps)):
            pt_[b, p] = next(perm)
    kp = jnp.zeros(pool_shape(num_pages, ps, nh, dh), jnp.float32)
    vp = jnp.zeros(pool_shape(num_pages, ps, nh, dh), jnp.float32)
    kp, vp = ao.kv_cache_prefill_write_fn(
        kp, vp, jnp.asarray(k), jnp.asarray(v), jnp.asarray(pt_),
        jnp.asarray(lens, np.int32))
    return k, v, kp, vp, jnp.asarray(pt_)


# -- op level: paged attention vs dense --------------------------------------

def test_paged_attention_matches_dense_ragged_rows():
    """XLA gather-based paged decode attention over a shuffled page table
    == dense attention per row, at three different context lengths."""
    import jax.numpy as jnp

    ps, nh, dh = 4, 2, 8
    lens = [5, 9, 1]
    k, v, kp, vp, pt_ = _scattered_pool(lens, ps, nh, dh, num_pages=16)
    q = _rand((3, nh, dh), 9)
    out = ao._paged_attention_reference(
        jnp.asarray(q), kp, vp, pt_, jnp.asarray(lens, np.int32),
        sm_scale=dh ** -0.5)
    for b, L_ in enumerate(lens):
        ref = ao._reference_attention(
            jnp.asarray(q[b:b + 1, :, None, :]),
            jnp.asarray(k[b:b + 1, :, :L_]), jnp.asarray(v[b:b + 1, :, :L_]),
            sm_scale=dh ** -0.5)
        np.testing.assert_allclose(np.asarray(out)[b],
                                   np.asarray(ref)[0, :, 0, :],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("ps,nh,dh,lens,bucket,block_pages", [
    (16, 12, 64, [40, 200, 16, 1, 0, 97], None, None),  # the cells' widths
    (8, 2, 64, [7, 12, 3, 1], None, None),       # one 128-lane column
    (8, 1, 128, [9, 0, 24], None, None),         # a head fills the register
    (8, 8, 16, [5, 17], None, None),             # eight heads to a register
    # the serving cells' own geometry: 64-row buckets of 32 pages, two
    # blocks of 16. A row that ends exactly on the block boundary (256), rows
    # whose second block is all dead, a kv_len 0 row between live rows
    (16, 12, 64, [256, 300, 0, 512, 1, 255, 257], 32, None),
    (16, 12, 64, [40, 100, 0, 17], 8, None),     # a bucket smaller than G
    # blocks of two pages over a bucket of five: a padded last block
    (8, 2, 64, [40, 16, 0, 33, 9], 5, 2),
    (8, 8, 16, [64, 0, 0, 1, 32], 8, 4),         # dead rows side by side
], ids=["cell_12x64_p16", "2x64_p8", "1x128_p8", "8x16_p8",
        "cell_P32_two_blocks", "bucket_below_G", "P5_blocks_of_2",
        "P8_blocks_of_4"])
def test_paged_attention_pallas_matches_reference(monkeypatch, ps, nh, dh,
                                                  lens, bucket, block_pages):
    """The Pallas page-DMA kernel (interpret mode on the CPU mesh) ==
    the XLA gather reference to float32 rounding, on the lane-dense pool:
    ragged lengths, a row that ends on a page boundary, a row of one token
    and a padded row (kv_len 0: zeros, read by nobody). Table entries past
    a row's live pages are out of range: nothing may fetch them."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    B = len(lens)
    P = bucket or max(1, max(-(-l // ps) for l in lens))
    num_pages = B * P + 3
    _, _, kp, vp, pt_ = _scattered_pool(
        [max(l, 1) for l in lens], ps, nh, dh, num_pages=num_pages, seed=3)
    table = np.full((B, P), num_pages + 7, np.int32)
    for b, l in enumerate(lens):
        n = -(-l // ps)
        table[b, :n] = np.asarray(pt_)[b, :n]
    table = jnp.asarray(table)
    q = jnp.asarray(_rand((B, nh, dh), 4))
    assert ppa.paged_supported(q.shape, kp.shape)
    kv = jnp.asarray(lens, np.int32)
    ref = ao._paged_attention_reference(q, kp, vp, table, kv,
                                        sm_scale=dh ** -0.5)
    monkeypatch.setattr(ppa, "INTERPRET", True)
    if block_pages:
        monkeypatch.setattr(ppa, "BLOCK_BYTES",
                            block_pages * 2 * ps * nh * dh * 4)
    assert ppa.pages_per_grid_step(P, ps, nh * dh, 4) == (
        block_pages or min(P, 16))
    out = ppa.paged_decode_attention(q, kp, vp, table, kv,
                                     sm_scale=dh ** -0.5)
    out, ref = np.asarray(out), np.asarray(ref)
    assert out.shape == (B, nh, dh)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-6, atol=2e-6)
    assert not out[~live].any()


def _butterfly_head_sums(x, head_dim):
    """The form `_head_sums` had until PR 38, kept as the oracle: at step
    s a lane adds its partner `lane ^ s`; float32 sums."""
    lane = np.arange(x.shape[1])
    s = 1
    while s < head_dim:
        x = x + np.where(lane & s != 0, np.roll(x, s, 1), np.roll(x, -s, 1))
        s *= 2
    return x


@pytest.mark.parametrize("head_dim", [8, 16, 32, 64, 128])
def test_head_sums_on_the_mxu_are_float32_sums(head_dim):
    """The `nkv == nh` arm's head sum alone: three bfloat16 pieces of the
    float32 products through a 0/1 matrix give the float32 sum the
    butterfly gave. The products of one head span 2^-20..2^20, so ONE
    bfloat16 pass is wrong by orders of magnitude where the three pieces
    are within float32 rounding of the exact sum."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    rng = np.random.default_rng(head_dim)
    x = (rng.choice([-1.0, 1.0], (64, 128)) * rng.uniform(1, 2, (64, 128))
         * 2.0 ** rng.integers(-20, 21, (64, 128))).astype(np.float32)
    heads = x.reshape(64, 128 // head_dim, head_dim).astype(np.float64)
    exact = np.repeat(heads.sum(-1), head_dim, axis=1)
    scale = np.repeat(np.abs(heads).sum(-1), head_dim, axis=1)
    # float32 rounding (three pieces read 2e-7 here, TWO read 2e-6 to 7e-6)
    rounding = 1e-6 * scale
    got = np.asarray(ppa._head_sums(jnp.asarray(x), head_dim))
    assert got.dtype == np.float32
    assert (np.abs(got - exact) <= rounding).all()
    assert (np.abs(got - _butterfly_head_sums(x, head_dim)) <= rounding).all()
    lane = np.arange(128)
    same_head = (lane[:, None] // head_dim == lane[None] // head_dim)
    one_pass = np.asarray(jnp.dot(
        jnp.asarray(x).astype(jnp.bfloat16),
        jnp.asarray(same_head, jnp.bfloat16),
        preferred_element_type=jnp.float32))
    assert (np.abs(one_pass - exact) > 100 * rounding).any()


def _all_eqns(jaxpr):
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("head_dim", [64, 16])
def test_column_update_sums_heads_without_a_lane_rotation(head_dim):
    """The arm's column body as the kernel traces it: no `roll` (the
    butterfly is gone) and the head sum is a `dot_general` of bfloat16
    operands accumulated in float32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    row = jnp.zeros((1, 128), jnp.float32)
    slab = jnp.zeros((ppa.LANE_CHUNK_TOKENS, 128), jnp.float32)
    eqns = list(_all_eqns(jax.make_jaxpr(
        lambda *a: ppa._column_update(*a, sm_scale=0.125, head_dim=head_dim)
    )(row, slab, slab, jnp.int32(40), row, row, row).jaxpr))
    assert not [e for e in eqns if "roll" in e.primitive.name]
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 3
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert e.outvars[0].aval.dtype == jnp.float32
        assert e.outvars[0].aval.shape == slab.shape


@pytest.mark.parametrize("nh,dh,ps,chosen", [
    (12, 64, 16, "pallas_paged"),    # nh*dh = 768: whole (8, 128) tiles
    (2, 64, 8, "pallas_paged"),
    (2, 8, 4, "xla"),                # nh*dh = 16: the rehearsal geometry
    (3, 32, 8, "xla"),               # nh*dh = 96, no multiple of 128
    (2, 64, 4, "xla"),               # a page is no whole sublane tile
    (1, 256, 8, "xla"),              # a head wider than a register
], ids=["12x64_p16", "2x64_p8", "2x8_p4", "3x32_p8", "2x64_p4", "1x256_p8"])
def test_paged_backend_follows_the_pool_shape(monkeypatch, nh, dh, ps,
                                              chosen):
    """Which path a decode shape takes is decided by the shape alone: a
    lane-dense page slab of whole tiles runs the kernel, every other
    geometry the XLA gather on the SAME pool shape — and both give the
    dense answer."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    monkeypatch.setattr(ppa, "INTERPRET", True)   # the kernel is runnable
    lens = [ps + 1, 2]
    k, v, kp, vp, pt_ = _scattered_pool(lens, ps, nh, dh, num_pages=6)
    assert kp.shape == (6, ps, nh * dh)
    q = jnp.asarray(_rand((2, nh, dh), 7))
    backend, _ = ao.paged_attention_backend(
        2, nh, pt_.shape[1] * ps, dh, np.dtype("float32"),
        pool_shape=kp.shape)
    assert backend == chosen
    before = dict(ao.dispatch_counts())
    out = ao.paged_decode_attention_fn(q, kp, vp, pt_,
                                       jnp.asarray(lens, np.int32),
                                       sm_scale=dh ** -0.5)
    ran = {k_: n - before.get(k_, 0)
           for k_, n in ao.dispatch_counts().items()
           if k_[0] == "paged" and n - before.get(k_, 0)}
    assert ran == {("paged", chosen, chosen): 1}
    for b, L_ in enumerate(lens):
        ref = ao._reference_attention(
            jnp.asarray(q[b:b + 1, :, None, :]),
            jnp.asarray(k[b:b + 1, :, :L_]), jnp.asarray(v[b:b + 1, :, :L_]),
            sm_scale=dh ** -0.5)
        np.testing.assert_allclose(np.asarray(out)[b],
                                   np.asarray(ref)[0, :, 0, :],
                                   rtol=2e-5, atol=2e-5)


def test_kv_append_page_boundary_and_mask():
    """Appends that land on a page boundary go to the next page's slot 0;
    masked (padded) rows write nothing."""
    import jax.numpy as jnp

    ps, nh, dh = 4, 2, 8
    kp = jnp.zeros(pool_shape(8, ps, nh, dh), jnp.float32)
    vp = jnp.zeros(pool_shape(8, ps, nh, dh), jnp.float32)
    pt_ = jnp.asarray([[5, 2], [3, 6]], np.int32)
    k = jnp.asarray(_rand((2, nh, dh), 0))
    v = jnp.asarray(_rand((2, nh, dh), 1))
    row0 = np.asarray(k)[0].reshape(nh * dh)    # head h at h*dh:(h+1)*dh
    # row 0 writes slot 3 (last of page 5); row 1 is masked out
    live = jnp.asarray([[1.0], [0.0]], np.float32)
    kp1, vp1 = ao.kv_cache_append_fn(kp, vp, k, v, pt_,
                                     jnp.asarray([3, 3], np.int32), live)
    np.testing.assert_allclose(np.asarray(kp1)[5, 3], row0)
    assert np.all(np.asarray(kp1)[3] == 0), "masked row wrote to its page"
    # row 0's next append (slot 4 == page boundary) lands in page 2 slot 0
    kp2, _ = ao.kv_cache_append_fn(kp1, vp1, k, v, pt_,
                                   jnp.asarray([4, 4], np.int32), live)
    np.testing.assert_allclose(np.asarray(kp2)[2, 0], row0)
    np.testing.assert_allclose(np.asarray(kp2)[5, 3], row0)


def _dense_write(pool, rows, page, slot):
    """The oracle's write: one token's [nh, dh] heads into row (page, slot)
    of a numpy pool [pages, ps, nh*dh], head h at columns h*dh:(h+1)*dh."""
    nh, dh = rows.shape
    for h in range(nh):
        pool[page, slot, h * dh:(h + 1) * dh] = rows[h]


def _oracle_append_boundary():
    """Appends at slots 3, 4 (a page boundary) and 5 of a row, beside a
    row that is masked throughout."""
    import jax.numpy as jnp

    ps, nh, dh = 4, 2, 8
    shape = pool_shape(8, ps, nh, dh)
    got = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
    want = (np.zeros(shape, np.float32), np.zeros(shape, np.float32))
    table = np.asarray([[5, 2], [3, 6]], np.int32)
    live = jnp.asarray([[1.0], [0.0]], np.float32)
    for step, slot in enumerate((3, 4, 5)):
        k, v = _rand((2, nh, dh), 10 + step), _rand((2, nh, dh), 20 + step)
        got = ao.kv_cache_append_fn(
            *got, jnp.asarray(k), jnp.asarray(v), jnp.asarray(table),
            jnp.asarray([slot, slot], np.int32), live)
        _dense_write(want[0], k[0], table[0, slot // ps], slot % ps)
        _dense_write(want[1], v[0], table[0, slot // ps], slot % ps)
    return got, want


def _oracle_append_masked():
    """No Mask input writes every row; an all-zero mask writes none; a
    position past the table's last page clamps to it (the engine never
    feeds one, the op must not write out of the row's pages)."""
    import jax.numpy as jnp

    ps, nh, dh = 4, 3, 8
    shape = pool_shape(6, ps, nh, dh)
    k, v = _rand((3, nh, dh), 1), _rand((3, nh, dh), 2)
    table = np.asarray([[0], [4], [2]], np.int32)
    pos = np.asarray([1, 3, 0], np.int32)
    zeros = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
    none = ao.kv_cache_append_fn(
        *zeros, jnp.asarray(k), jnp.asarray(v), jnp.asarray(table),
        jnp.asarray(pos), jnp.zeros((3, 1), jnp.float32))
    assert not np.any(np.asarray(none[0])) and not np.any(np.asarray(none[1]))
    got = ao.kv_cache_append_fn(*zeros, jnp.asarray(k), jnp.asarray(v),
                                jnp.asarray(table), jnp.asarray(pos))
    want = (np.zeros(shape, np.float32), np.zeros(shape, np.float32))
    for b in range(3):
        _dense_write(want[0], k[b], table[b, 0], pos[b])
        _dense_write(want[1], v[b], table[b, 0], pos[b])
    return got, want


def _oracle_prefill_write(start):
    """A bucket-padded window of 7 tokens a row: without Start token s
    goes to slot s, with Start to slot Start+s; positions past Lens and
    rows of length 0 write nothing."""
    import jax.numpy as jnp

    ps, nh, dh, S = 4, 2, 8, 7
    shape = pool_shape(12, ps, nh, dh)
    k, v = _rand((3, nh, S, dh), 5), _rand((3, nh, S, dh), 6)
    table = np.asarray([[7, 1, 9], [0, 4, 2], [3, 5, 6]], np.int32)
    lens = np.asarray([7, 0, 3], np.int32)
    base = np.asarray(start if start is not None else [0, 0, 0], np.int32)
    got = ao.kv_cache_prefill_write_fn(
        jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
        jnp.asarray(k), jnp.asarray(v), jnp.asarray(table),
        jnp.asarray(lens),
        None if start is None else jnp.asarray(base))
    want = (np.zeros(shape, np.float32), np.zeros(shape, np.float32))
    for b in range(3):
        for s_ in range(lens[b]):
            g = base[b] + s_
            _dense_write(want[0], k[b, :, s_], table[b, g // ps], g % ps)
            _dense_write(want[1], v[b, :, s_], table[b, g // ps], g % ps)
    return got, want


def _oracle_window_attention():
    """Suffix prefill: a window of 3 queries behind cached prefixes of 5
    and 9 tokens attends the whole pooled context, == dense causal
    attention over prefix + window at the window's positions."""
    import jax.numpy as jnp

    ps, nh, dh, S = 4, 2, 8, 3
    prefix = [5, 9]
    total = [p_ + S for p_ in prefix]
    k, v, kp, vp, pt_ = _scattered_pool(total, ps, nh, dh, num_pages=12,
                                        seed=11)
    q = _rand((2, nh, S, dh), 12)
    got = ao.paged_prefill_attention_fn(
        jnp.asarray(q), kp, vp, pt_, jnp.asarray(prefix, np.int32),
        sm_scale=dh ** -0.5)
    want = np.zeros((2, nh, S, dh), np.float32)
    for b, (p_, t) in enumerate(zip(prefix, total)):
        full_q = np.zeros((1, nh, t, dh), np.float32)
        full_q[0, :, p_:] = q[b]
        ref = ao._reference_attention(
            jnp.asarray(full_q), jnp.asarray(k[b:b + 1, :, :t]),
            jnp.asarray(v[b:b + 1, :, :t]), causal=True,
            sm_scale=dh ** -0.5)
        want[b] = np.asarray(ref)[0, :, p_:]
    return (got,), (want,)


def _oracle_cow_copy():
    """Copy-on-write's copy through the op itself: page Dst becomes page
    Src for K and V, every other page keeps its bytes."""
    import types

    import jax.numpy as jnp

    from paddle_tpu.ops.registry import ExecContext

    shape = pool_shape(6, 4, 2, 8)
    kp, vp = _rand(shape, 30), _rand(shape, 31)
    op = types.SimpleNamespace(
        inputs={s_: [s_] for s_ in ("KPool", "VPool", "Src", "Dst")},
        attrs={})
    out = ao.kv_cache_copy_page_op(ExecContext(op, {
        "KPool": jnp.asarray(kp), "VPool": jnp.asarray(vp),
        "Src": jnp.asarray([4], np.int32),
        "Dst": jnp.asarray([1], np.int32)}))
    want = (kp.copy(), vp.copy())
    want[0][1], want[1][1] = kp[4], vp[4]
    return (out["KPoolOut"], out["VPoolOut"]), want


@pytest.mark.parametrize("case", [
    _oracle_append_boundary, _oracle_append_masked,
    lambda: _oracle_prefill_write(None),
    lambda: _oracle_prefill_write([2, 1, 6]),
    _oracle_window_attention, _oracle_cow_copy,
], ids=["append_page_boundary", "append_masked_rows", "prefill_write",
        "prefill_write_start", "window_attention_prefix", "cow_copy"])
def test_paged_cache_ops_match_dense_oracle(case):
    """Each paged-cache op on the `[pages, page_size, nh*dh]` pool against
    a plain numpy oracle of the same semantics."""
    got, want = case()
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert np.asarray(g).shape == w.shape
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("program", ["decode", "prefill", "window", "cow",
                                     "decode_tp2"])
def test_serving_programs_donate_the_pools(program):
    """Every serving program updates the pools in place: the buffers it
    was handed are donated (deleted once the step has run) and the scope
    holds the step's outputs, of the one pool shape."""
    from paddle_tpu.serving.kv_cache import pool_var_names
    from tools.pool_hlo import serving_program_cases

    cfg = decoder_tiny()
    eng = ServingEngine(cfg, page_size=4, pool_pages=16, max_inflight=2,
                        tp=2 if program.endswith("_tp2") else 1)
    names = [n for pair in pool_var_names(cfg.num_layers) for n in pair]
    target, feed, fetches = serving_program_cases(
        eng, rows=2, pages=2, prompt=8)[program.removesuffix("_tp2")]

    def run():
        eng._exe.run(target, feed=feed, fetch_list=fetches,
                     scope=eng._scope)

    run()    # under tp the first run also moves the state onto the mesh
    before = [eng._scope.find_var(n) for n in names]
    run()
    shape = pool_shape(16, 4, cfg.num_heads, cfg.head_dim)
    for n, old in zip(names, before):
        new = eng._scope.find_var(n)
        assert old.is_deleted(), f"{program}: {n} was copied, not donated"
        assert not new.is_deleted() and new.shape == shape


def test_paged_backend_tuner_lever(tmp_path):
    """A swept DB entry drives the decode-attention backend for its exact
    (b, nh, 1, sk, dh) key; an un-runnable pallas verdict (off-TPU, no
    interpreter) degrades to the reference at dispatch — numerics exact."""
    import jax.numpy as jnp

    snap = pt.flags.all_flags()
    db_path = str(tmp_path / "db.json")
    try:
        pt.flags.set_flags({"tuning_mode": "consult", "tuning_db": db_path})
        tuning.invalidate_db_cache()
        ps, nh, dh = 4, 2, 8
        lens = [6, 2]
        _, _, kp, vp, pt_ = _scattered_pool(lens, ps, nh, dh, num_pages=8)
        P = pt_.shape[1]
        key = tuning.canonical_key(
            "attention", tuning.attention_key(2, nh, 1, P * ps, dh, True),
            "float32", tuning.device_kind())
        db = tuning.TuningDB(db_path)
        db.put(key, {"backend": "pallas_paged"}, source="swept")
        db.save(db_path)
        tuning.invalidate_db_cache()
        backend, tier = ao.paged_attention_backend(2, nh, P * ps, dh,
                                                   np.dtype("float32"),
                                                   pool_shape=kp.shape)
        assert (backend, tier) == ("pallas_paged", "db")
        q = jnp.asarray(_rand((2, nh, dh), 5))
        out = ao.paged_decode_attention_fn(q, kp, vp, pt_,
                                           jnp.asarray(lens, np.int32),
                                           sm_scale=dh ** -0.5)
        ref = ao._paged_attention_reference(q, kp, vp, pt_,
                                            jnp.asarray(lens, np.int32),
                                            sm_scale=dh ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)
    finally:
        pt.flags.set_flags(snap)
        tuning.invalidate_db_cache()


def test_decode_candidate_upgrades_via_tune(tmp_path, monkeypatch):
    """The PR 6 candidates workflow extended to decode attention: a
    sq=1 candidate key recorded by a sweep-mode run is measured and
    upgraded to a swept verdict by tools/tune.py."""
    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa
    from tools import tune

    monkeypatch.setattr(ppa, "INTERPRET", True)  # both arms runnable on CPU
    pt.flags.set_flags({"serving_page_size": 8})
    try:
        db_path = str(tmp_path / "db.json")
        db = tuning.TuningDB(db_path)
        key = tuning.canonical_key(
            "attention", tuning.attention_key(2, 2, 1, 16, 64, True),
            "float32", tuning.device_kind())   # nh*dh = 128: a kernel shape
        db.put(key, {"backend": "xla"}, source="candidate")
        tune.sweep_candidates(db, iters=1, passes=2, band=0.05)
        entry = db.lookup(key)
        assert entry["source"] == "swept"
        assert entry["decision"]["backend"] in ("xla", "pallas_paged")
        assert {"xla", "pallas_paged"} <= set(entry["measured"])
    finally:
        pt.flags.set_flags({"serving_page_size": 16})


@pytest.mark.parametrize("pallas", [True, False], ids=["pallas", "xla"])
def test_decode_grid_steps_counts_rows_times_page_blocks(monkeypatch, pallas):
    """`serving.decode_grid_steps` books, for every plain decode step the
    Pallas arm serves, the grid of ONE layer's call, padded rows x page
    blocks of the bucket (G from the kernel's own `pages_per_grid_step`),
    and stays 0 where the XLA gather serves the same pool."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa
    from paddle_tpu.serving import DecoderConfig

    monkeypatch.setattr(ppa, "INTERPRET", pallas)
    # blocks of two pages: a page's K + V is 2 x [8, 128] float32
    monkeypatch.setattr(ppa, "BLOCK_BYTES", 2 * 2 * 8 * 128 * 4)
    cfg = DecoderConfig(vocab_size=64, hidden_size=128, num_layers=1,
                        num_heads=2, ffn_size=128, max_position=64)
    eng = ServingEngine(cfg, page_size=8, pool_pages=32, max_inflight=4,
                        seed=0)
    eng.reset_stats()          # the registry's serving.* series start at 0
    seen = []
    run_step = eng._run_step

    def spy(kind, target, io, feed, *args, **kwargs):
        if kind == "decode":
            seen.append(feed[sv_model.PAGES_FEED].shape)
        return run_step(kind, target, io, feed, *args, **kwargs)

    monkeypatch.setattr(eng, "_run_step", spy)
    before = dict(ao.dispatch_counts())
    for n in (5, 20, 33):
        eng.submit(list(range(1, n + 1)), max_new_tokens=4)
    eng.run_until_drained()
    assert seen and max(pb for _, pb in seen) > 2     # several blocks a row
    ran = {k: n - before.get(k, 0) for k, n in ao.dispatch_counts().items()
           if k[0] == "paged" and n != before.get(k, 0)}
    assert {k[2] for k in ran} == {"pallas_paged" if pallas else "xla"}
    want = sum(bb * -(-pb // 2) for bb, pb in seen) if pallas else 0
    assert eng.stats["decode_grid_steps"] == want
    assert obs.snapshot()["counters"].get(
        "serving.decode_grid_steps", 0) == want
    assert eng.stats["decode_context_pages"] > 0


# -- pool allocator ----------------------------------------------------------

def test_pool_allocator_edges():
    pool = PagedKVPool(4, 8)
    assert pool.pages_for(1) == 1 and pool.pages_for(8) == 1
    assert pool.pages_for(9) == 2
    got = pool.allocate(3)
    assert len(got) == 3 and pool.free_count == 1
    assert pool.allocate(2) is None, "partial grabs must not happen"
    assert pool.free_count == 1
    pool.free(got)
    assert pool.free_count == 4
    with pytest.raises(ValueError, match="double-free"):
        pool.free([got[0], got[0]])
    with pytest.raises(ValueError, match="outside pool"):
        pool.free([99])


def test_pool_counts_the_pages_only_a_cache_holds():
    """`cache_only` (what admission counts free beside the free list) is
    the indexed pages with one holder, at every step of a page's life:
    indexed under its writer, left to the cache, hit, released, evicted;
    the audit recounts it."""
    pool = PagedKVPool(8, 4)
    cache = PrefixCache(pool)
    tokens = list(range(12))
    pages = pool.allocate(3)
    assert cache.insert(tokens, pages) == 3 and pool.cache_only == 0
    pool.release(pages)                     # the writer leaves
    assert pool.cache_only == 3 and pool.free_count == 5
    hit = cache.match(tokens[:8])
    pool.share(hit)                         # a reader maps two of them
    assert pool.cache_only == 1
    assert cache.evict(8) == 1 and pool.cache_only == 0
    assert pool.check_consistency() == []
    pool.release(hit)
    assert pool.cache_only == 2
    pool.cache_only += 1
    assert any("cache_only" in p for p in pool.check_consistency())
    pool.cache_only -= 1
    assert cache.clear() == 2 and pool.cache_only == 0
    pool.reset()
    assert pool.free_count == 8 and pool.check_consistency() == []


def test_pool_counts_the_pages_a_holder_alone_would_return():
    """`sole_count` (what the timeline of the ends counts returned when a
    row leaves) is the pages of a table that no other table maps: free, or
    the cache's alone, once it lets go; through an owner's view too."""
    pool = PagedKVPool(8, 4)
    cache = PrefixCache(pool)
    row = pool.allocate(4)
    assert pool.sole_count(row) == 4
    cache.insert(list(range(8)), row)       # the cache indexes two of them
    assert pool.sole_count(row) == 4
    other = cache.match(list(range(4)))
    pool.share(other)                       # a second row maps the first
    assert pool.sole_count(row) == 3 and pool.sole_count(other) == 0
    assert OwnedPoolView(pool, "a").sole_count(row) == 3
    pool.release(other)
    assert pool.sole_count(row) == 4
    pool.release(row)
    assert pool.cache_only == 2 and pool.free_count == 6


def _assert_no_leaks(eng):
    """The ISSUE 11 leak contract: every in-use page is accounted for by a
    live request or a prefix-cache entry, and flushing the cache returns
    the WHOLE pool to the free list."""
    assert eng.leaked_pages() == 0, f"{eng.leaked_pages()} orphaned pages"
    eng.flush_prefix_cache()
    assert eng.pool.free_count == eng.pool.num_pages, (
        f"{eng.pool.num_pages - eng.pool.free_count} pages still held "
        f"after drain + cache flush")


# -- engine: equivalence against dense attention -----------------------------

def test_engine_generation_matches_dense_oracle():
    """The whole serving path (bucketed prefill -> paged ragged decode over
    scattered pages, with requests of different lengths batched together)
    greedy-generates EXACTLY what a dense full-context forward does."""
    cfg = decoder_tiny()
    eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4)
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in (3, 9, 17)]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_drained()

    full = Program()
    with program_guard(full, Program()), unique_name.guard():
        io = build_full_forward_program(cfg)
    for p, rid in zip(prompts, rids):
        seq = list(p)
        for _ in range(6):
            feed = {sv_model.TOK_FEED: np.asarray(seq, np.int32)[None, :],
                    sv_model.POS_FEED:
                        np.arange(len(seq), dtype=np.int32)[None, :]}
            (lg,) = eng._exe.run(full, feed=feed,
                                 fetch_list=[io["logits"]],
                                 scope=eng._scope)
            seq.append(int(np.argmax(lg[0, -1])))
        assert eng.result(rid) == seq[len(p):], f"request {rid} diverged"
    _assert_no_leaks(eng)


# -- engine: scheduling edge cases -------------------------------------------

def test_pool_exhaustion_backpressures_admission():
    """More requests than the pool can hold at once: admission queues them
    (never crashes, never oversubscribes) and every request still
    finishes once earlier ones release pages."""
    cfg = decoder_tiny()
    # 6 pages of 4 slots: one 9-token prompt + decode needs 3 pages, so at
    # most two requests fit concurrently
    eng = ServingEngine(cfg, page_size=4, pool_pages=6, max_inflight=8)
    rng = np.random.default_rng(1)
    rids = [eng.submit(list(rng.integers(1, cfg.vocab_size, 9)),
                       max_new_tokens=3) for _ in range(5)]
    eng.run_until_drained()
    assert all(eng.requests[r].state == "finished" for r in rids)
    assert eng.stats["peak_pages_in_use"] <= eng.pool.num_pages
    _assert_no_leaks(eng)


def test_oversize_request_raises_cleanly():
    cfg = decoder_tiny()
    eng = ServingEngine(cfg, page_size=4, pool_pages=2, max_inflight=2)
    with pytest.raises(ValueError, match="max_position"):
        eng.submit(list(range(1, 80)), max_new_tokens=60)
    # fits max_position but can never fit the 2-page pool: surfaced, not hung
    eng.submit(list(np.random.default_rng(0).integers(1, 97, 20)),
               max_new_tokens=2)
    with pytest.raises(RuntimeError, match="pool"):
        eng.run_until_drained()


def test_preemption_recomputes_exactly():
    """Mid-decode pool exhaustion preempts the youngest request; its
    re-prefilled continuation produces the SAME tokens a pressure-free pool
    yields (greedy decode + recompute preemption is exact). Prefix caching
    off: the PR 7 bitwise-recompute contract is for the plain engine —
    with the cache, a re-admission reuses its own cached prompt pages
    through the suffix path, whose last-bit drift is the same class the
    dense-oracle test tolerates but not bitwise the cold prefill."""
    cfg = decoder_tiny()
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in (7, 7)]

    big = ServingEngine(cfg, page_size=2, pool_pages=64, max_inflight=2,
                        prefix_cache=False)
    want = []
    for p in prompts:
        rid = big.submit(p, max_new_tokens=8)
        big.run_until_drained()
        want.append(big.result(rid))

    # 16 pages of 2 slots hold both requests to their ends (15 slots each):
    # both are admitted, and the younger is preempted by hand, twice, with
    # tokens of its own to prefill again
    small = ServingEngine(cfg, page_size=2, pool_pages=16, max_inflight=2,
                          prefix_cache=False)
    rids = [small.submit(p, max_new_tokens=8) for p in prompts]
    with preempting(small):
        small.run_until_drained()
    assert small.stats["preemptions"] == 2
    assert small.requests[rids[1]].preemptions == 2
    assert [small.result(r) for r in rids] == want
    assert small.pool.free_count == small.pool.num_pages


def test_sjf_policy_admits_shortest_first():
    cfg = decoder_tiny()
    eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=1,
                        policy="sjf")
    rng = np.random.default_rng(5)
    long_rid = eng.submit(list(rng.integers(1, 97, 20)), max_new_tokens=2)
    short_rid = eng.submit(list(rng.integers(1, 97, 3)), max_new_tokens=2)
    eng.step()  # max_inflight=1: exactly one admission — sjf picks short
    assert eng.requests[short_rid].state in ("running", "finished")
    assert eng.requests[long_rid].state == "waiting"
    eng.run_until_drained()
    assert eng.requests[long_rid].state == "finished"


def test_knobs_are_fixed_when_the_engine_is_built():
    """The nine runtime knobs resolve once, in the constructor, from its
    arguments and then the flags: flipping every flag afterwards to a value
    that would shed, expire or stall the same traffic moves neither the
    engine's values nor what it admits and emits."""
    hostile = {"serving_max_inflight": 1, "serving_draft_k": 3,
               "serving_deadline_s": 1e-9, "serving_priority_default": 0,
               "serving_shed_occupancy": 0.01, "serving_shed_queue_depth": 1,
               "serving_shed_ttft_p99_ms": 1e-6, "serving_degrade_after": 1,
               "serving_audit_every": 1}
    knobs = [name[len("serving_"):] for name in hostile]
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(1, 97, 6 + i)) for i in range(4)]

    def serve(eng):
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]  # no reject
        eng.step()
        admitted = sum(eng.requests[r].state != "waiting" for r in rids)
        eng.run_until_drained()
        assert all(eng.requests[r].state == "finished" for r in rids)
        return admitted, [eng.result(r) for r in rids]

    def build():    # arguments for two knobs, the flags for the other seven
        return ServingEngine(decoder_tiny(), page_size=4, pool_pages=64,
                             max_inflight=2, degrade_after=2, seed=0)

    snap = pt.flags.all_flags()
    want_knobs = {"max_inflight": 2, "degrade_after": 2, **{
        k: snap[f"serving_{k}"] for k in knobs
        if k not in ("max_inflight", "degrade_after")}}
    want = serve(build())
    eng = build()
    try:
        pt.flags.set_flags(hostile)
        assert {k: getattr(eng, k) for k in knobs} == want_knobs
        assert eng._slo is None         # no TTFT floor when it was built
        assert serve(eng) == want and want[0] == 2
        assert eng.stats["shed"] == eng.stats["rejects"] == 0
        assert eng.stats["deadline_exceeded"] == eng.stats["spec_steps"] == 0
        # and an engine built NOW takes the flags' values
        late = ServingEngine(decoder_tiny(), page_size=4, pool_pages=64)
        assert {k: getattr(late, k) for k in knobs} == {
            k: hostile[f"serving_{k}"] for k in knobs}
    finally:
        pt.flags.set_flags(snap)


# -- compile discipline ------------------------------------------------------

def test_decode_compiles_once_per_bucket():
    """The compile-count contract (reusing the PR 2 jit_compile_counter
    hook): a full run compiles decode exactly once per (batch-bucket,
    page-bucket) signature, and a second identical wave through the same
    engine compiles NOTHING."""
    from paddle_tpu.pipeline import jit_compile_counter

    cfg = decoder_tiny()
    eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4)
    rng = np.random.default_rng(11)

    def wave():
        rids = [eng.submit(list(rng.integers(1, 97, n)), max_new_tokens=4)
                for n in (3, 5, 9, 12)]
        eng.run_until_drained()
        return rids

    with jit_compile_counter() as c1:
        wave()
    n_sigs = (len(eng.stats["prefill_signatures"])
              + len(eng.stats["decode_signatures"]))
    assert c1.count == n_sigs, (
        f"{c1.count} XLA compiles for {n_sigs} distinct bucket signatures "
        f"(prefill {eng.stats['prefill_signatures']}, decode "
        f"{eng.stats['decode_signatures']})")
    with jit_compile_counter() as c2:
        wave()
    assert c2.count == 0, (
        f"second wave recompiled {c2.count}x — bucketing failed to hit "
        f"the compile cache")


# -- chaos: aborted requests leak nothing ------------------------------------

@pytest.mark.chaos
def test_abort_mid_decode_returns_pages_over_cycles():
    """`serving_abort` fault site extended to SHARED-PREFIX requests
    (ISSUE 11): every cycle submits requests sharing a system prompt, so
    aborts hit requests whose page tables map refcounted shared pages.
    An abort must decrement refcounts — never free a page another request
    (or the prefix cache) still maps — and after every drain the zero-leak
    accounting must balance; at the end, flushing the cache returns the
    WHOLE pool."""
    from paddle_tpu.resilience.faults import fault_scope

    cfg = decoder_tiny()
    eng = ServingEngine(cfg, page_size=4, pool_pages=32, max_inflight=4)
    rng = np.random.default_rng(13)
    sys_prompt = list(rng.integers(1, 97, 8))  # page-aligned: COW territory
    total_aborts = 0
    for cycle in range(3):
        with fault_scope("serving_abort:2,4") as plan:
            rids = [eng.submit(sys_prompt + list(rng.integers(1, 97, n)),
                               max_new_tokens=6) for n in (0, 5, 10)]
            eng.run_until_drained()
            assert plan.stats()["fired"], "abort plan never fired"
        states = {eng.requests[r].state for r in rids}
        assert states <= {"finished", "aborted"}
        assert "aborted" in states, f"cycle {cycle}: nothing was aborted"
        total_aborts += sum(1 for r in rids
                            if eng.requests[r].state == "aborted")
        assert eng.leaked_pages() == 0, (
            f"cycle {cycle} orphaned {eng.leaked_pages()} pages")
        # cached shared pages survive the cycle with exactly the cache's ref
        for node in eng.prefix_cache._nodes.values():
            assert eng.pool.refcount(node.page) >= 1
    assert eng.stats["aborts"] == total_aborts
    assert eng.stats["prefix_hit_tokens"] > 0, "no prefix sharing exercised"
    eng.flush_prefix_cache()
    assert eng.pool.free_count == eng.pool.num_pages
