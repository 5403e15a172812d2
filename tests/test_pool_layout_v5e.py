"""The KV pool keeps one device layout (tools/pool_hlo.py): the check on two
short HLO texts recorded from the v5e's compiler, and the four serving
programs compiled here for a described v5e (nothing runs; libtpu compiles
for a chip that is not attached). The only test file that loads libtpu."""
import pytest

import chip_smoke
from paddle_tpu.serving import DecoderConfig
from tools.pool_hlo import (kernel_calls, pool_sized_copies, sorts_over,
                            token_row_gathers, updates_out_of_place)

POOL = 3072 * 16 * 12 * 64

# PR 23's decode program (parent of PR 24), one K pool of one layer: the
# resident buffer is pages-minor-most, the scatter and the kernel want it
# row-major, the output goes back. Instruction attributes trimmed.
HLO_RELAYOUT = """\
HloModule jit_fn, is_scheduled=true, input_output_alias={ {2}: (34, {}, may-alias) }

%fused_computation.3 (param_0.9: f32[3072,16,12,64], param_1.14: s32[64], param_2.16: f32[64,12,64]) -> f32[3072,16,12,64] {
  %param_0.9 = f32[3072,16,12,64]{3,2,1,0:T(8,128)} parameter(0)
  %param_2.16 = f32[64,12,64]{2,1,0:T(8,128)} parameter(2)
  %param_1.14 = s32[64]{0:T(128)} parameter(1)
  ROOT %scatter.0 = f32[3072,16,12,64]{3,2,1,0:T(8,128)} scatter(%param_0.9, %param_1.14, %param_2.16), update_window_dims={1,2}, inserted_window_dims={0,1}, scatter_dims_to_operand_dims={0,1}, index_vector_dim=1, to_apply=%region_5.15
}

ENTRY %main.31 (feed_vals_0_.1: f32[64,1], rw_vals_0_.1: f32[3072,16,12,64]) -> (s32[64], f32[3072,16,12,64]) {
  %rw_vals_0_.1 = f32[3072,16,12,64]{0,3,2,1:T(8,128)} parameter(34), sharding={replicated}, metadata={op_name="rw_vals[0]"}
  %copy.22 = f32[3072,16,12,64]{3,2,1,0:T(8,128)} copy(%rw_vals_0_.1), sharding={replicated}, metadata={op_name="rw_vals[0]"}
  %fusion.3 = f32[3072,16,12,64]{3,2,1,0:T(8,128)} fusion(%copy.22, %fusion.237, %get-tuple-element.27), kind=kCustom, calls=%fused_computation.3, metadata={op_name="jit(fn)/scatter" stack_frame_id=45}
  %paged_decode_attention.2 = f32[64,12,64]{2,1,0:T(8,128)S(1)} custom-call(%copy-done.14, %copy-done.34, %copy-done.11, %fusion.3, %fusion.4), custom_call_target="tpu_custom_call"
  %copy.27 = f32[3072,16,12,64]{0,3,2,1:T(8,128)} copy(%fusion.3), backend_config={"flag_configs":[]}
  ROOT %tuple.16 = (s32[64]{0:T(128)}, f32[3072,16,12,64]{0,3,2,1:T(8,128)}) tuple(%copy-done.46, %copy.27)
}
"""

# PR 24's decode program, the same pool as [3072, 16, 768], and its
# copy-on-write program (a loop fusion that ends in a dynamic-update-slice).
HLO_IN_PLACE = """\
HloModule jit_fn, is_scheduled=true, input_output_alias={ {2}: (34, {}, may-alias) }

%fused_computation.3 (param_0.9: f32[3072,16,768], param_1.14: s32[64], param_2.16: f32[64,768]) -> f32[3072,16,768] {
  %param_0.9 = f32[3072,16,768]{2,1,0:T(8,128)} parameter(0)
  %param_2.16 = f32[64,768]{1,0:T(8,128)} parameter(2)
  %param_1.14 = s32[64]{0:T(128)} parameter(1)
  ROOT %scatter.0 = f32[3072,16,768]{2,1,0:T(8,128)} scatter(%param_0.9, %param_1.14, %param_2.16), update_window_dims={1}, inserted_window_dims={0,1}, scatter_dims_to_operand_dims={0,1}, index_vector_dim=1, to_apply=%region_5.15
}

%fused_computation.1 (param_0.1: f32[3072,16,768], param_1.3: s32[], param_2.8: f32[1,16,768]) -> f32[3072,16,768] {
  %param_0.1 = f32[3072,16,768]{2,1,0:T(8,128)} parameter(0)
  %param_2.8 = f32[1,16,768]{2,1,0:T(8,128)} parameter(2)
  %param_1.3 = s32[]{:T(128)} parameter(1)
  ROOT %dynamic-update-slice.3 = f32[3072,16,768]{2,1,0:T(8,128)} dynamic-update-slice(%param_0.1, %param_2.8, %param_1.3, %constant.25, %constant.25)
}

ENTRY %main.31 (feed_vals_0_.1: f32[64,1], rw_vals_0_.1: f32[3072,16,768]) -> (s32[64], f32[3072,16,768]) {
  %rw_vals_0_.1 = f32[3072,16,768]{2,1,0:T(8,128)} parameter(34), sharding={replicated}, metadata={op_name="rw_vals[0]"}
  %fusion.3 = f32[3072,16,768]{2,1,0:T(8,128)} fusion(%rw_vals_0_.1, %fusion.253, %reshape.232), kind=kCustom, calls=%fused_computation.3, metadata={op_name="jit(fn)/scatter" stack_frame_id=46}
  %paged_decode_attention.2 = f32[64,1,768]{2,1,0:T(1,128)S(1)} custom-call(%broadcast_clamp_fusion, %get-tuple-element.9, %reshape.261, %fusion.3, %fusion.4), custom_call_target="tpu_custom_call"
  %fusion.9 = f32[3072,16,768]{2,1,0:T(8,128)} fusion(%fusion.3, %select_n.3, %constant_dynamic-slice_fusion), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(cow)/scatter"}
  %bitcast.4 = f32[49152,768]{1,0:T(8,128)} bitcast(%fusion.9)
  ROOT %tuple.13 = (s32[64]{0:T(128)}, f32[3072,16,768]{2,1,0:T(8,128)}) tuple(%copy-done.46, %fusion.9)
}
"""


def test_pool_sized_copies_finds_the_parents_relayout():
    found = pool_sized_copies(HLO_RELAYOUT, POOL)
    assert [(c["name"], c["op"], c["from_layout"], c["layout"])
            for c in found] == [
        ("copy.22", "copy", "{0,3,2,1:T(8,128)}", "{3,2,1,0:T(8,128)}"),
        ("copy.27", "copy", "{3,2,1,0:T(8,128)}", "{0,3,2,1:T(8,128)}")]
    assert all(c["shape"] == "f32[3072,16,12,64]" for c in found)
    # another pool size in the same text is not this pool's business
    assert pool_sized_copies(HLO_RELAYOUT, POOL // 2) == []


def test_pool_sized_copies_passes_in_place_updates():
    """Scatter and dynamic-update-slice fusions, the kernel's custom call,
    parameters, bitcasts and tuples are not copies, whatever the metadata
    says; a loop fusion that ends in anything else is."""
    assert pool_sized_copies(HLO_IN_PLACE, POOL) == []
    rewritten = HLO_IN_PLACE.replace(
        "ROOT %dynamic-update-slice.3 = f32[3072,16,768]{2,1,0:T(8,128)} "
        "dynamic-update-slice(", "ROOT %select.3 = f32[3072,16,768]"
        "{2,1,0:T(8,128)} select(")
    assert [c["name"] for c in pool_sized_copies(rewritten, POOL)] == [
        "fusion.9"]


# The parent of PR 44's decode program of `nemotron3_super_120b` (described
# v5e): the pool of convolution tails as `[800, 30720]`, the first mixer's
# scatter TWICE (rematerialised), both reading the parameter, so the first
# cannot write into it. Two of the five mixers, attributes trimmed.
TAILS = 800 * 30720
HLO_SCATTER_TWICE = """\
HloModule jit_serving_decode, is_scheduled=true, input_output_alias={ {7}: (35, {}, may-alias) }

%fused_computation.12 (param_0.38: f32[800,30720], param_1.126: s32[1024]) -> f32[128,30720] {
  %param_0.38 = f32[800,30720]{1,0:T(8,128)} parameter(0)
  %param_1.126 = s32[1024]{0:T(1024)} parameter(1)
  ROOT %gather.5 = f32[128,30720]{1,0:T(8,128)} gather(%param_0.38, %param_1.126), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,30720}
}

%fused_computation.19 (param_0.55: f32[800,30720], param_1.92: s32[128], param_2.86: f32[128,30720]) -> f32[800,30720] {
  %param_0.55 = f32[800,30720]{1,0:T(8,128)} parameter(0)
  %param_1.92 = s32[128]{0:T(128)} parameter(1)
  %param_2.86 = f32[128,30720]{1,0:T(8,128)} parameter(2)
  ROOT %scatter.23 = f32[800,30720]{1,0:T(8,128)} scatter(%param_0.55, %param_1.92, %param_2.86), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%region_13.24
}

%fused_computation.18.clone (param_0.1591: f32[800,30720], param_1.1654: s32[128], param_2.1394: f32[128,30720]) -> f32[800,30720] {
  %param_0.1591 = f32[800,30720]{1,0:T(8,128)} parameter(0)
  %param_1.1654 = s32[128]{0:T(128)} parameter(1)
  %param_2.1394 = f32[128,30720]{1,0:T(8,128)} parameter(2)
  ROOT %scatter.42 = f32[800,30720]{1,0:T(8,128)} scatter(%param_0.1591, %param_1.1654, %param_2.1394), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%region_2.6
}

%fused_computation.18.clone.clone (param_0.1592: f32[800,30720], param_1.1655: s32[128], param_2.1395: f32[128,30720]) -> f32[800,30720] {
  %param_0.1592 = f32[800,30720]{1,0:T(8,128)} parameter(0)
  %param_1.1655 = s32[128]{0:T(128)} parameter(1)
  %param_2.1395 = f32[128,30720]{1,0:T(8,128)} parameter(2)
  ROOT %scatter.43 = f32[800,30720]{1,0:T(8,128)} scatter(%param_0.1592, %param_1.1655, %param_2.1395), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%region_2.6
}

ENTRY %main.74 (feed_vals_0_.1: f32[128,1], rw_vals_4_.1: f32[800,30720]) -> (s32[128], f32[800,30720]) {
  %rw_vals_4_.1 = f32[800,30720]{1,0:T(8,128)} parameter(35), sharding={replicated}, metadata={op_name="rw_vals[4]"}
  %fusion.12 = f32[128,30720]{1,0:T(8,128)} fusion(%rw_vals_4_.1, %pad_clamp_fusion.4), kind=kCustom, calls=%fused_computation.12, metadata={op_name="jit(serving_decode)/mixer_moe_stack/decode/conv/gather"}
  %fusion.18.remat = f32[800,30720]{1,0:T(8,128)} fusion(%rw_vals_4_.1, %copy-done.108, %copy.364), kind=kCustom, calls=%fused_computation.18.clone, metadata={op_name="jit(serving_decode)/mixer_moe_stack/decode/conv/scatter"}
  %fusion.13 = f32[128,30720]{1,0:T(8,128)} fusion(%fusion.18.remat, %pad_clamp_fusion.3), kind=kCustom, calls=%fused_computation.12, metadata={op_name="jit(serving_decode)/mixer_moe_stack/decode/conv/gather"}
  %fusion.18.remat2 = f32[800,30720]{1,0:T(8,128)} fusion(%rw_vals_4_.1, %copy-done.109, %custom-call.52), kind=kCustom, calls=%fused_computation.18.clone.clone, metadata={op_name="jit(serving_decode)/mixer_moe_stack/decode/conv/scatter"}
  %fusion.19 = f32[800,30720]{1,0:T(8,128)} fusion(%fusion.18.remat2, %copy-done.122, %copy.382), kind=kCustom, calls=%fused_computation.19, metadata={op_name="jit(serving_decode)/mixer_moe_stack/decode/conv/scatter"}
  ROOT %tuple.232 = (s32[128]{0:T(128)}, f32[800,30720]{1,0:T(8,128)}) tuple(%copy-done.123, %fusion.19)
}
"""

# PR 44's: the same pool as `[800, 240, 128]`, handed from the parameter
# through one `conv_decode_update` a mixer, each aliasing it to its output.
HLO_KERNEL_CHAIN = """\
HloModule jit_serving_decode, is_scheduled=true, input_output_alias={ {7}: (35, {}, may-alias) }

ENTRY %main.74 (feed_vals_0_.1: f32[128,1], rw_vals_4_.1: f32[800,240,128]) -> (s32[128], f32[800,240,128]) {
  %rw_vals_4_.1 = f32[800,240,128]{2,1,0:T(8,128)} parameter(35), sharding={replicated}, metadata={op_name="rw_vals[4]"}
  %conv_decode_update.10 = (f32[800,240,128]{2,1,0:T(8,128)}, f32[128,80,128]{2,1,0:T(8,128)S(1)}) custom-call(%get-tuple-element.349, %rw_vals_4_.1, %bitcast.605, %copy_bitcast_fusion.4, %bitcast.655), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{0}: (1, {})}
  %jit__call_.32 = f32[800,240,128]{2,1,0:T(8,128)} get-tuple-element(%conv_decode_update.10), index=0
  %conv_decode_update.11 = (f32[800,240,128]{2,1,0:T(8,128)}, f32[128,80,128]{2,1,0:T(8,128)S(1)}) custom-call(%copy-done.97, %jit__call_.32, %bitcast.607, %copy_bitcast_fusion.3, %bitcast.654), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{0}: (1, {})}
  %jit__call_.38 = f32[800,240,128]{2,1,0:T(8,128)} get-tuple-element(%conv_decode_update.11), index=0
  ROOT %tuple.232 = (s32[128]{0:T(128)}, f32[800,240,128]{2,1,0:T(8,128)}) tuple(%copy-done.123, %jit__call_.38)
}
"""


def test_updates_out_of_place_names_the_scatter_whose_pool_is_read_again():
    """An update whose pool operand has a later user cannot write into it:
    the first of the rematerialised pair is named, the second (the
    parameter's last user) and the scatter behind it are not."""
    assert updates_out_of_place(HLO_SCATTER_TWICE, TAILS) == [{
        "name": "fusion.18.remat", "op": "fusion",
        "shape": "f32[800,30720]", "pool": "rw_vals_4_.1",
        "read_again_by": "fusion.18.remat2"}]
    # the instruction itself passes as an in-place update
    assert pool_sized_copies(HLO_SCATTER_TWICE, TAILS) == []
    assert updates_out_of_place(HLO_SCATTER_TWICE, TAILS // 2) == []


def test_updates_out_of_place_passes_a_pool_handed_from_update_to_update():
    """A chain of aliasing kernel calls, and PR 24's programs, whose pool
    is read by the attention kernel BEFORE the copy-on-write updates it:
    an earlier reader holds nothing."""
    assert updates_out_of_place(HLO_KERNEL_CHAIN, TAILS) == []
    assert pool_sized_copies(HLO_KERNEL_CHAIN, TAILS) == []
    assert updates_out_of_place(HLO_IN_PLACE, POOL) == []
    late = HLO_IN_PLACE.replace(
        "bitcast(%fusion.9)", "bitcast(%fusion.3)")
    assert [u["name"] for u in updates_out_of_place(late, POOL)] == [
        "fusion.9"]


@pytest.fixture(scope="module")
def v5e_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def test_serving_programs_compiled_for_v5e_move_no_pool(v5e_chip):
    """Decode (with the Mosaic-compiled paged kernel), prefill, window and
    copy-on-write at the serving cells' widths and pool, two layers deep,
    compiled by the chip's own compiler: no pool-sized copy in any. A
    compile that passes is not a chip run; chip_smoke.py repeats it there."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        out = chip_smoke.pool_layout_phase(
            DecoderConfig(num_layers=2), page_size=16, pool_pages=3072,
            rows=64, device=v5e_chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert out["pool_sized_copies"] == {
        "decode": 0, "prefill": 0, "window": 0, "cow": 0}


def test_cca_moe_programs_compiled_for_v5e_move_no_pool(v5e_chip):
    """The same four programs of the "cca_moe" block at ZAYA1-8B's widths
    (benchmark/configs/zaya1_8b.json), two layers deep over a stacked pool
    as large as the cell's (2 x 7,680 = 24 x 640 pages of 128 bfloat16
    slots): Mosaic takes the expert kernel and the grouped-query paged
    kernel at their real shapes, and the scanned layer carries the stacked
    pools without a copy."""
    import json
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "zaya1_8b.json")) as f:
        engine = json.load(f)["engine"]
    cfg = DecoderConfig(**dict(engine["config_kwargs"], num_layers=2))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        out = chip_smoke.pool_layout_phase(
            cfg, page_size=engine["page_size"],
            pool_pages=engine["pool_pages"] * 12, rows=64, device=v5e_chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert out["pool_sized_copies"] == {
        "decode": 0, "prefill": 0, "window": 0, "cow": 0}


def test_sparse_moe_programs_compiled_for_v5e_move_no_pool(v5e_chip):
    """The four programs of the "sparse_moe" block at the served widths
    (benchmark/configs/keye_vl2_30b_a3b.json: 32 query / 4 KV heads of 128,
    an indexer of 16 heads of 64 keeping 2,048 positions, 128 experts of
    width 768 top-8, the whole vocabulary), one layer deep over stacked
    pools as large as the cell's (1 x 10,752 = 6 x 1,792 pages of 128
    bfloat16 slots: a one-layer index pool would fit the chip's VMEM and be
    prefetched there whole), decode at 64 rows over the cell's 288-page
    tables: Mosaic takes the expert kernel at F = 768, and the scanned
    layer carries the joined K/V rows and the per-token indexer-key pool
    without a copy of either. The decode program fetches a selected token
    ONCE, as a row of 512 words (PR 29's fetched it from a K pool and from
    a V pool, and the chip gathers by the row: PERF.md, PR 30) and names its
    2,048 without a sort (PR 34); the windows read whole pages and gather no
    token."""
    import json
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        engine = json.load(f)["engine"]
    cfg = DecoderConfig(**dict(engine["config_kwargs"], num_layers=1))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        out = chip_smoke.pool_layout_phase(
            cfg, page_size=engine["page_size"],
            pool_pages=engine["pool_pages"] * 6, rows=64, device=v5e_chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert out["pool_sized_copies"] == {
        "decode": 0, "prefill": 0, "window": 0, "cow": 0}
    assert out["token_row_gathers"] == {
        "decode": 1, "prefill": 0, "window": 0, "cow": 0}
    # nor does any sort a row's 36,864 scores (decode did until PR 34)
    assert not any(out["context_sorts"].values()), out["context_sorts"]
    # a decode row's 288 key pages are read where they lie, by the paged
    # indexer kernel (PR 40); the windows gather theirs once for all queries
    assert out["paged_indexer_calls"] == {
        "decode": 1, "prefill": 0, "window": 0, "cow": 0}


def test_hybrid_moe_programs_compiled_for_v5e_move_neither_pool(v5e_chip):
    """The four programs of the "hybrid_moe" block at the served widths
    (benchmark/configs/laguna_xs2.json: layers 0-4, 48 and 64 query heads
    over 8 KV heads of 128, window 512, the dense layer of 8,192, experts of
    512 top-8, the whole vocabulary; 16 experts a layer where the cell
    holds 256, which sizes nothing but the weights drawn here) over both
    pools as large as the cell's (2 x 2,048 and 3 x 768 pages of 128
    bfloat16 slots), decode at 64 rows over the cell's 160-page tables and
    5-page window tables: Mosaic takes the grouped-query paged kernel at 48
    heads and, with a first live slot, at 64, and the expert kernel at F =
    512; the unrolled layers carry four pools without a copy of any."""
    import json
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna_xs2.json")) as f:
        engine = json.load(f)["engine"]
    cfg = DecoderConfig(**dict(engine["config_kwargs"], num_experts=16))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        out = chip_smoke.pool_layout_phase(
            cfg, page_size=engine["page_size"],
            pool_pages=engine["pool_pages"], rows=64, device=v5e_chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert out["pool_sized_copies"] == {
        "decode": 0, "prefill": 0, "window": 0, "cow": 0}


def test_parallel_ssm_programs_compiled_for_v5e_move_no_pool(v5e_chip):
    """The five programs of the "parallel_ssm" block at the served widths
    (benchmark/configs/falcon_h1_34b.json: 20 query heads over 4 KV heads of
    128 beside 32 state-space heads of 128 x 256 in 2 groups, the SwiGLU of
    21,504; two layers and a vocabulary of 2,048, which size nothing but the
    weights drawn here) over the cell's K/V pool and 80 slots of state,
    decode at 64 rows over the cell's one 24-page bucket: Mosaic takes the
    one-token state update in place in the slot pool and the grouped-query
    paged kernel at 24 heads (groups of 5 padded to 6); the scanned layer
    carries K, V and the slots without a copy of any, a window's scan reads
    and writes its slot where it lies, and the state copy moves two slots'
    rows and not the pool."""
    import json
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import numpy as np

    from paddle_tpu.serving import ServingEngine
    from tools.pool_hlo import serving_program_hlos

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "falcon_h1_34b.json")) as f:
        engine = json.load(f)["engine"]
    cfg = DecoderConfig(**dict(engine["config_kwargs"], num_layers=2,
                               vocab_size=2048))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        eng = ServingEngine(cfg, page_size=engine["page_size"],
                            pool_pages=engine["pool_pages"], max_inflight=64)
        texts = serving_program_hlos(eng, rows=64, pages=24,
                                     device=v5e_chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    # K, V and the slots of state (the convolution's tails, 29 MB at the
    # served size, the compiler moves into fast memory for a decode step
    # and back: not a relayout, and not counted)
    sizes = {int(np.prod(eng._scope.find_var(name).shape))
             for name in ("kv_cache.k", "kv_cache.ssm")}
    assert {name: sum(len(pool_sized_copies(text, n)) for n in sizes)
            for name, text in texts.items()} == {
        "decode": 0, "prefill": 0, "window": 0, "cow": 0, "state_copy": 0}
    assert "ssm_decode_update" in texts["decode"]
    # a decode token's convolution moves its tail on in its slot (PR 44)
    tails = eng._scope.find_var("kv_cache.conv").shape
    assert tails == (2 * 80, 120, 128)
    assert "conv_decode_update" in texts["decode"]
    assert updates_out_of_place(texts["decode"], int(np.prod(tails))) == []
    assert "paged_decode_attention_gqa" in texts["decode"]
    assert "f32[64,24,128]" in texts["decode"]      # 20 heads run as 24
    # the copy slices a slot's rows out of the pool: no gather, and nothing
    # of the pool's size but the pool
    assert " gather(" not in texts["state_copy"]
    slots = eng.state_pool.num_pages
    assert slots == 80
    assert f"f32[{2 * slots},2048,128]" not in texts["state_copy"]


def test_mixer_moe_programs_compiled_for_v5e_move_no_pool(v5e_chip):
    """The "mixer_moe" block at the served widths (two mixers, an expert
    layer with 8 experts held, an attention layer, a small vocabulary): the
    state pool whose slots hold
    two 64-wide heads a lane row keeps its layout through every program
    (the one attention layer's K and V are not judged here: at this cut's
    few pages the compiler stages a pool for the decode step, which it does
    not do at the served 4,096 pages; chip_smoke-style checks on the chip
    read the served size),
    the windows' pack and unpack included (written as a transpose they
    copied the whole pool there and back: PR 43), and the three Pallas
    kernels are in the decode program."""
    import json
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import numpy as np

    from paddle_tpu.serving import ServingEngine
    from tools.pool_hlo import serving_program_hlos

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron3_super_120b.json")) as f:
        engine = json.load(f)["engine"]
    # two mixers: with one the transposed form showed no copy either
    cfg = DecoderConfig(**dict(engine["config_kwargs"], num_layers=4,
                               layer_pattern="MEM*", experts_held=8,
                               vocab_size=2048))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        eng = ServingEngine(cfg, page_size=engine["page_size"],
                            pool_pages=1032, max_inflight=32)
        texts = serving_program_hlos(eng, rows=32, pages=36,
                                     device=v5e_chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    slots = eng.state_pool.num_pages
    assert eng._scope.find_var("kv_cache.ssm").shape == (2 * slots,
                                                         64 * 128, 128)
    states = int(np.prod(eng._scope.find_var("kv_cache.ssm").shape))
    assert {name: len(pool_sized_copies(text, states))
            for name, text in texts.items()} == {
        "decode": 0, "prefill": 0, "window": 0, "cow": 0, "state_copy": 0}
    assert "ssm_decode_update" in texts["decode"]
    # each mixer's convolution hands the pool of tails on in place (PR 44:
    # XLA's scatter into `[rows, 30720]` passed over the pool a mixer); at
    # this cut's 80 slots the compiler stages the 9.8 MB pool in fast
    # memory for the step, which it does not at the served 98 MB
    tails = eng._scope.find_var("kv_cache.conv").shape
    assert tails == (2 * slots, 240, 128)
    assert texts["decode"].count("conv_decode_update.") >= 2
    assert updates_out_of_place(texts["decode"], int(np.prod(tails))) == []
    assert "moe_relu2_experts_decode" in texts["decode"]
    assert "moe_relu2_experts_prefill" in texts["window"]
    assert "paged_decode_attention_gqa" in texts["decode"]
    assert "f32[32,32,128]" in texts["decode"]      # groups of 16, unpadded
    assert " gather(" not in texts["state_copy"]


def test_latent_moe_stack_compiled_for_v5e_moves_neither_pool(v5e_chip,
                                                              monkeypatch):
    """The "latent_moe" stack at the served widths
    (benchmark/configs/deepseek_v32_exp.json: 128 heads of 128 + 64 over a
    512-value latent, an indexer of 64 heads of 128 keeping 2,048, 16 held
    experts of width 2,048 at hidden 7,168), a dense and a routed layer
    deep, over pools as large as the cell's (5 x 2,304 pages), from SHAPES
    alone (9 GB of weights are not drawn here): the decode step at 128 rows
    and a 256-token window, both behind the cell's 288-page tables. Mosaic
    takes the expert kernel at the narrow F tile (three slabs of 7168 x 512
    do not fit); the latent rows are kept in whole 128-lane tiles (288
    words in 384: as `[.., 128, 288]` the chip stores the pool slots-minor
    and every step copied it, 1.7 GB each way), so neither pool is copied;
    a decode row gathers a selected token ONCE, as one row, and nothing
    sorts a row's 36,864 scores."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops import latent_moe_ops as ops
    from paddle_tpu.ops import decoder_common
    from paddle_tpu.ops.pallas_kernels import workbench
    from paddle_tpu.serving import kv_cache
    from paddle_tpu.serving import model as sv_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "deepseek_v32_exp.json")) as f:
        engine = json.load(f)["engine"]
    served = DecoderConfig(**engine["config_kwargs"])
    cfg = DecoderConfig(**dict(engine["config_kwargs"], num_layers=2))
    pages, ps = engine["pool_pages"], engine["page_size"]
    one_chip = SingleDeviceSharding(v5e_chip)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {key: shape(dims, dtype) for key, (dims, dtype, _) in
              sv_model._latent_param_specs(cfg).items()}
    pools = tuple(shape(dims, dtype) for _, dims, dtype in
                  kv_cache.stacked_pool_shapes(
                      *sv_model._latent_pool_geometry(served, pages, ps)))
    assert [p.shape for p in pools] == [(5 * pages, ps, 384),
                                        (5 * pages, 128, ps)]
    geom = ops.Geometry(**sv_model._latent_geometry(cfg))
    weights = (params["dec.word_emb"], params["dec.lm_head"],
               params["dec.final_norm.scale"],
               {k: params["dense." + k]
                for k in ops.ATTENTION_PARAMS + ops.DENSE_PARAMS},
               {k: params["moe." + k]
                for k in ops.ATTENTION_PARAMS + ops.MOE_PARAMS},
               tuple(params[k] for k in ops.EXPERT_PARAMS))
    # on the chip the expert layer is the kernel (here jax sees a CPU), and
    # so are a decode step's indexer scores
    monkeypatch.setattr(decoder_common, "_experts_backend",
                        lambda *a: "pallas")
    monkeypatch.setattr(workbench, "on_tpu", lambda: True)

    def compiled(mode, tok_shape, rows):
        def step(tok, pos, weights, pools, table, lens, start, mask, mark):
            return ops.latent_moe_stack_fn(
                mode, tok, pos, *weights, geom, pools=pools,
                page_table=table, lens=lens, start=start, mask=mask,
                mark=mark, num_pages=pages)

        i32 = "int32"
        return jax.jit(step, donate_argnums=(3,)).lower(
            shape(tok_shape, i32), shape(tok_shape, i32), weights, pools,
            shape((rows, 288), i32), shape((rows,), i32),
            shape((rows,), i32), shape((rows, 1), "float32"),
            shape((sv_model.MARK_ROWS,), i32)).compile().as_text()

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        texts = {"decode": compiled("decode", (128,), 128),
                 "window": compiled("window", (1, 256), 1)}
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    sizes = [5 * pages * ps * 384, 5 * pages * 128 * ps]
    for name, text in texts.items():
        assert not [c for n in sizes for c in pool_sized_copies(text, n)], \
            name
        assert "tpu_custom_call" in text and "moe_topk_experts" in text
        assert not sorts_over(text, 288 * ps), name
        # the dense layer's gather and the scanned routed layer's
        assert len(token_row_gathers(text, 384)) == 2, name
    # a decode row's key pages are read where they lie, by the kernel (the
    # dense layer's call and the scanned layer's); a window gathers its
    # pages once for all its queries
    assert {name: kernel_calls(text, "paged_indexer_scores")
            for name, text in texts.items()} == {"decode": 2, "window": 0}
    # the absorbed attention over the gathered rows is the kernel in both
    # (a layer kind each; a window's inside its loop over query blocks):
    # neither the `[queries, heads, 2,048]` scores nor an unpacked copy of
    # the rows is written
    assert {name: kernel_calls(text, "latent_rows_attention")
            for name, text in texts.items()} == {"decode": 2, "window": 2}
    for text in texts.values():
        assert "f32[128,128,2048]" not in text \
            and "f32[64,128,2048]" not in text \
            and "bf16[128,2048,512]" not in text \
            and "bf16[64,2048,512]" not in text


def test_latent_streams_stack_compiled_for_v5e_reads_its_pages_in_place(
        v5e_chip, monkeypatch):
    """The "latent_moe" stack WITHOUT an indexer and with four residual
    streams at the served widths (benchmark/configs/xing4_29b_a4b.json: 32
    heads of 128 + 64 over a 512-value latent, 64 experts of width 1,024 at
    hidden 3,584), a dense and a routed layer deep, over a pool as large as
    the cell's (7 x 1,792 pages, ONE pool), from SHAPES alone: the decode
    step at 64 rows and windows of 256 and 2,048 tokens, all behind the
    cell's 288-page tables. Mosaic takes the paged attention kernel (a
    layer kind each); the pool is not copied, no cached row is gathered in a
    decode step, and a window gathers its pages once and never holds the
    `[heads, queries, 36,864]` scores (its keys run in blocks of 512)."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops import latent_moe_ops as ops
    from paddle_tpu.ops import decoder_common
    from paddle_tpu.ops.pallas_kernels import workbench
    from paddle_tpu.serving import kv_cache
    from paddle_tpu.serving import model as sv_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "xing4_29b_a4b.json")) as f:
        engine = json.load(f)["engine"]
    served = DecoderConfig(**engine["config_kwargs"])
    cfg = DecoderConfig(**dict(engine["config_kwargs"], num_layers=2,
                               dense_layers=1))
    pages, ps = engine["pool_pages"], engine["page_size"]
    one_chip = SingleDeviceSharding(v5e_chip)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {key: shape(dims, dtype) for key, (dims, dtype, _) in
              sv_model._latent_param_specs(cfg).items()}
    pools = tuple(shape(dims, dtype) for _, dims, dtype in
                  kv_cache.stacked_pool_shapes(
                      *sv_model._latent_pool_geometry(served, pages, ps)))
    assert [p.shape for p in pools] == [(7 * pages, ps, 384)]
    geom = ops.Geometry(**sv_model._latent_geometry(cfg))
    shared = ops.attention_params(False, cfg.hc_mult)
    assert "wqi" not in shared and shared[-3:] == ("hc_w", "hc_a", "hc_b")
    weights = (params["dec.word_emb"], params["dec.lm_head"],
               params["dec.final_norm.scale"],
               {k: params["dense." + k] for k in shared + ops.DENSE_PARAMS},
               {k: params["moe." + k] for k in shared + ops.MOE_PARAMS},
               tuple(params[k] for k in ops.EXPERT_PARAMS))
    monkeypatch.setattr(decoder_common, "_experts_backend",
                        lambda *a: "pallas")
    monkeypatch.setattr(workbench, "on_tpu", lambda: True)

    def compiled(mode, tok_shape, rows):
        def step(tok, pos, weights, pools, table, lens, start, mask):
            return ops.latent_moe_stack_fn(
                mode, tok, pos, *weights, geom, pools=pools,
                page_table=table, lens=lens, start=start, mask=mask,
                num_pages=pages)

        i32 = "int32"
        return jax.jit(step, donate_argnums=(3,)).lower(
            shape(tok_shape, i32), shape(tok_shape, i32), weights, pools,
            shape((rows, 288), i32), shape((rows,), i32),
            shape((rows,), i32), shape((rows, 1), "float32")).compile()

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        programs = {"decode": compiled("decode", (64,), 64),
                    "window256": compiled("window", (1, 256), 1),
                    "window2048": compiled("window", (1, 2048), 1)}
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    texts = {name: c.as_text() for name, c in programs.items()}
    for name, text in texts.items():
        assert not pool_sized_copies(text, 7 * pages * ps * 384), name
        assert "tpu_custom_call" in text and "moe_topk_experts" in text
        assert "f32[1,32,256,36864]" not in text \
            and "f32[1,32,2048,36864]" not in text, name
    # a decode row's pages are read where they lie, by the kernel (the dense
    # layer's call and the scanned layer's); no token row is gathered and no
    # page slab either
    assert {name: kernel_calls(text, "paged_latent_attention")
            for name, text in texts.items()} \
        == {"decode": 2, "window256": 0, "window2048": 0}
    assert not token_row_gathers(texts["decode"], 384)
    assert "s32[64,288,128,384]" not in texts["decode"]
    # what a step holds besides weights and pool: under 64 MB for the
    # decode step, under 600 MB for a 2,048-token window
    temp = {name: c.memory_analysis().temp_size_in_bytes
            for name, c in programs.items()}
    assert temp["decode"] < 64e6 and temp["window2048"] < 600e6, temp


def test_kda_moe_stack_compiled_for_v5e_moves_no_pool(v5e_chip, monkeypatch):
    """The "kda_moe" stack at the served widths (benchmark/configs/
    ling3_flash.json: 32 Kimi-Delta heads of 128 x 128, a convolution over
    12,288 channels, 32 latent heads of 128 + 64 over a 512-value latent, 128
    held experts of width 768 at hidden 2,560), two layers deep (one
    Kimi-Delta layer with a dense SwiGLU, one latent layer with experts),
    over pools as large as the cell's (6,144 pages of latent rows in ONE
    layer, 5 x 320 slots of state), from SHAPES alone: the decode step at
    256 rows and a 2,048-token window, behind the cell's 112-page tables.
    Mosaic takes the three in-place kernels of a decode step (the
    delta-rule update, the convolution, the paged latent attention) and the
    experts' stream; no program copies a pool, and the state pool is
    updated in place."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops import kda_ops as ops
    from paddle_tpu.ops import decoder_common
    from paddle_tpu.ops.pallas_kernels import workbench
    from paddle_tpu.serving import kv_cache
    from paddle_tpu.serving import model as sv_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ling3_flash.json")) as f:
        engine = json.load(f)["engine"]
    served = DecoderConfig(**engine["config_kwargs"])
    cfg = DecoderConfig(**dict(engine["config_kwargs"], num_layers=2,
                               layer_group_size=2, dense_layers=1))
    assert (served.mixer_kinds, served.mlp_kinds) == ("KKKKKL", "DDEEEE")
    assert (cfg.mixer_kinds, cfg.mlp_kinds) == ("KL", "DE")
    pages, ps, slots = engine["pool_pages"], engine["page_size"], 320
    one_chip = SingleDeviceSharding(v5e_chip)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {key: shape(dims, dtype) for key, (dims, dtype, _) in
              sv_model._kda_param_specs(cfg).items()}
    kv, state = sv_model.ssm_pool_geometry(served, pages, ps, slots)
    pools = tuple(shape(dims, dtype) for _, dims, dtype in
                  kv_cache.stacked_pool_shapes(*kv)
                  + kv_cache.state_pool_shapes(*state))
    assert [p.shape for p in pools] == [
        (pages, ps, 384), (5 * slots, 32 * 128, 128), (5 * slots, 288, 128)]
    geom = ops.Geometry(**sv_model._kda_geometry(cfg))
    weights = (params["dec.word_emb"], params["dec.lm_head"],
               params["dec.final_norm.scale"], params["norm"]) + tuple(
        {k: params[prefix + k] for k in keys}
        for _, prefix, keys in sv_model._KDA_GROUPS[:4]) + (
        tuple(params[k] for k in ops.EXPERT_PARAMS),)
    monkeypatch.setattr(decoder_common, "_experts_backend",
                        lambda *a: "pallas")
    monkeypatch.setattr(workbench, "on_tpu", lambda: True)

    def compiled(mode, tok_shape, rows):
        def step(tok, pos, weights, pools, table, lens, start, mask, slot):
            return ops.kda_moe_stack_fn(
                mode, tok, pos, *weights, geom, pools=pools,
                page_table=table, lens=lens, start=start, mask=mask,
                state_slot=slot, num_pages=pages, num_slots=slots)

        i32 = "int32"
        return jax.jit(step, donate_argnums=(3,)).lower(
            shape(tok_shape, i32), shape(tok_shape, i32), weights, pools,
            shape((rows, 112), i32), shape((rows,), i32),
            shape((rows,), i32), shape((rows, 1), "float32"),
            shape((rows,), i32)).compile()

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        programs = {"decode": compiled("decode", (256,), 256),
                    "window2048": compiled("window", (1, 2048), 1)}
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    texts = {name: c.as_text() for name, c in programs.items()}
    state_values = 5 * slots * 32 * 128 * 128
    for name, text in texts.items():
        assert not pool_sized_copies(text, pages * ps * 384), name
        assert not pool_sized_copies(text, state_values), name
        assert "moe_topk_experts" in text, name
    for kernel in ("kda_decode_update", "conv_decode_update",
                   "paged_latent_attention"):
        assert kernel in texts["decode"], kernel
        assert kernel not in texts["window2048"], kernel
    assert not updates_out_of_place(texts["decode"], state_values)
    # what a step holds besides weights and pools
    temp = {name: c.memory_analysis().temp_size_in_bytes
            for name, c in programs.items()}
    assert temp["decode"] < 400e6 and temp["window2048"] < 1.6e9, temp


def test_looped_dense_stack_compiled_for_v5e_moves_no_plane(v5e_chip,
                                                            monkeypatch):
    """The "looped_dense" stack at the served sizes (benchmark/configs/
    ouro_2_6b.json: ALL 48 layers of hidden 2,048, 16 heads of 128, four
    visits), over pools as large as the cell's (192 planes of 296 pages of
    16 tokens: 56,832 rows of 2,048 bfloat16 values a pool, 3.72 GB each),
    from SHAPES alone: the decode step at 32 rows and a 512-token window,
    behind the cell's 80-page tables. The layer's body is compiled ONCE
    (one call of the paged kernel in a decode program of 192 layer visits:
    since PR 55 the matrix-unit arm as the list walk, its plan outside both
    loops), Mosaic takes that kernel at one head a KV head in bfloat16
    pages of 16, and neither program copies a pool: the visits' planes are
    written through both loops in place."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops import looped_dense_ops as ops
    from paddle_tpu.ops.pallas_kernels import workbench
    from paddle_tpu.serving import kv_cache
    from paddle_tpu.serving import model as sv_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ouro_2_6b.json")) as f:
        engine = json.load(f)["engine"]
    cfg = DecoderConfig(**engine["config_kwargs"])
    pages, ps = engine["pool_pages"], engine["page_size"]
    assert (cfg.num_layers, cfg.loop_steps, cfg.cache_planes, ps) \
        == (48, 4, 192, 16)
    one_chip = SingleDeviceSharding(v5e_chip)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {key: shape(dims, dtype) for key, (dims, dtype, _) in
              sv_model._looped_param_specs(cfg).items()}
    pools = tuple(shape(dims, dtype) for _, dims, dtype in
                  kv_cache.stacked_pool_shapes(
                      *sv_model.stacked_pool_geometry(cfg, pages, ps)))
    assert [(p.shape, str(p.dtype)) for p in pools] \
        == [((192 * pages, 16, 2048), "bfloat16")] * 2
    geom = ops.Geometry(**sv_model._looped_geometry(cfg))
    weights = (params["dec.word_emb"], params["dec.lm_head"],
               params["dec.final_norm.scale"], params["dec.exit_gate.w"],
               params["dec.exit_gate.b"],
               {k: params[k] for k in ops.LAYER_PARAMS})
    table = -(-cfg.max_position // ps)
    monkeypatch.setattr(workbench, "on_tpu", lambda: True)

    def compiled(mode, tok_shape, rows):
        def step(tok, pos, weights, pools, table, lens, start, mask):
            return ops.looped_dense_stack_fn(
                mode, tok, pos, *weights, geom, pools=pools,
                page_table=table, lens=lens, start=start, mask=mask,
                num_pages=pages)

        i32 = "int32"
        return jax.jit(step, donate_argnums=(3,)).lower(
            shape(tok_shape, i32), shape(tok_shape, i32), weights, pools,
            shape((rows, table), i32), shape((rows,), i32),
            shape((rows,), i32), shape((rows, 1), "float32")).compile()

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        programs = {"decode": compiled("decode", (32,), 32),
                    "window512": compiled("window", (1, 512), 1)}
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    texts = {name: c.as_text() for name, c in programs.items()}
    values = 192 * pages * ps * 2048
    for name, text in texts.items():
        assert not pool_sized_copies(text, values), name
        assert not updates_out_of_place(text, values), name
    # 192 layer visits, ONE copy of the layer's body
    assert kernel_calls(texts["decode"], "paged_decode_attention") == 1
    assert kernel_calls(texts["window512"], "paged_decode_attention") == 0
    # weights once and pools: 12.8 GB of arguments, 5.34 of them parameters
    args = programs["decode"].memory_analysis().argument_size_in_bytes
    assert 12.7e9 < args < 12.9e9, args


def test_token_row_gathers_counts_rows_not_slabs():
    """Recorded from the v5e's compiler: PR 29's decode layer fetched a
    selected token from two pools, PR 30's from one; a page's slab of
    indexer keys and a table lookup are no token rows."""
    two = """\
  %gather.33 = bf16[64,2048,512]{2,1,0:T(8,128)(2,1)} gather(%param_0.17, %transpose.103), offset_dims={2}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=2, slice_sizes={1,512}, metadata={op_name="jit(fn)/while/body/closed_call/gather"}
  %gather.34 = bf16[64,2048,512]{2,1,0:T(8,128)(2,1)} gather(%param_0.18, %transpose.104), offset_dims={2}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=2, slice_sizes={1,512}
  %gather.31 = bf16[64,288,64,128]{3,2,1,0:T(8,128)(2,1)} gather(%param_0.14, %transpose.97), offset_dims={2,3}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=2, slice_sizes={1,64,128}
  %gather.37 = s32[64]{0:T(128)} gather(%param_0.26, %custom-call.8), offset_dims={}, collapsed_slice_dims={0,1}, start_index_map={0,1}, index_vector_dim=1, slice_sizes={1,1}
"""
    one = "  ROOT %gather.33 = s32[64,2048,512]{2,1,0:T(8,128)} gather(" \
          "%param_0.17, %transpose.103), offset_dims={2}, " \
          "collapsed_slice_dims={0}, slice_sizes={1,512}\n"
    assert [g["name"] for g in token_row_gathers(two, 512)] \
        == ["gather.33", "gather.34"]
    assert [g["shape"][:16] for g in token_row_gathers(one, 512)] \
        == ["s32[64,2048,512]"]
    assert token_row_gathers(two, 128) == []


def test_sorts_over_finds_the_context_sort_and_passes_a_routers():
    """Recorded from the v5e's compiler: `lax.top_k` over a decode row's
    36,864 scores (PR 29-33) is a stable sort of keys and positions
    together; a sort of a row of 128 router probabilities, or along
    another dimension, is no sort of a context."""
    text = """\
  %sort = (f32[64,36864]{1,0:T(8,128)S(1)}, s32[64,36864]{1,0:T(8,128)S(1)}) sort(%copy_bitcast_fusion, %iota), dimensions={1}, is_stable=true, to_apply=%compare-greater-than.1, metadata={op_name="jit(old_sort)/top_k"}
  %sort.1 = (f32[64,128]{1,0:T(8,128)}, s32[64,128]{1,0:T(8,128)}) sort(%fusion.3, %iota.2), dimensions={1}, is_stable=true, to_apply=%compare-greater-than.2
  ROOT %sort.2 = f32[36864,64]{1,0:T(8,128)} sort(%fusion.4), dimensions={1}, to_apply=%compare.3
"""
    assert sorts_over(text, 36864) == [
        {"name": "sort", "shape": "f32[64,36864]"}]
    assert [f["name"] for f in sorts_over(text, 64)] \
        == ["sort", "sort.1", "sort.2"]
    assert sorts_over(text, 36865) == []


@pytest.mark.parametrize("q_shape,pool,dtype,bucket", [
    ((64, 12, 64), (3072, 16, 768), "float32", 32),
    ((64, 8, 128), (24 * 640, 128, 256), "bfloat16", 16),
    ((16, 12, 64), (3072, 16, 768), "float32", 16),
    ((32, 16, 128), (192 * 296, 16, 2048), "bfloat16", 80),
], ids=["post_ln_P32", "cca_moe_P16_stacked", "post_ln_16rows_P16",
        "looped_dense_P80_stacked"])
def test_paged_decode_blocks_fit_their_vmem_budget_on_v5e(
        v5e_chip, q_shape, pool, dtype, bucket):
    """The blocked decode kernel alone at the serving cells' sizes (64 rows;
    a bucket of 32 pages of 16 float32 slots, and of 16 pages of 128
    bfloat16 slots over the stacked pool; the chat cell's 16 rows of 16
    pages; the looped cell's 32 rows of 80 pages of 16 bfloat16 slots, one
    head of 128 a KV head: the matrix-unit arm on the grid, the form a
    first live slot would take): a grid step covers 16 pages, its K + V block stays under
    `BLOCK_BYTES` (the kernel holds two: 4 MB of the chip's 16 MB of scoped
    VMEM), and Mosaic takes it with the pools left in HBM."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    _, ps, width = pool
    itemsize = jnp.dtype(dtype).itemsize
    group = ppa.pages_per_grid_step(bucket, ps, width, itemsize)
    assert group == 16
    assert group * 2 * ps * width * itemsize <= ppa.BLOCK_BYTES
    assert ppa.grid_steps(q_shape[0], bucket, ps, width, itemsize) \
        == q_shape[0] * bucket // 16
    sh = SingleDeviceSharding(v5e_chip)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=sh) for shape, dt in (
        (q_shape, jnp.float32), (pool, dtype), (pool, dtype),
        ((q_shape[0], bucket), jnp.int32), ((q_shape[0],), jnp.int32))]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda *a: ppa._call(*a, 0.125, False)).lower(
            *args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    assert pool_sized_copies(text, pool[0] * ps * width) == []


@pytest.mark.parametrize("q_shape,pool,bucket,tiles", [
    ((64, 48, 128), (2 * 2048, 128, 1024), 160, (16, 8)),
    ((64, 24, 128), (6 * 768, 128, 512), 24, (16, 8)),
    ((128, 32, 128), (4096, 128, 256), 64, (8, 4)),
    ((2, 8, 128), (24 * 640, 128, 256), 32, (16, 8)),
    ((32, 16, 128), (192 * 296, 16, 2048), 80, (16, 8)),
], ids=["laguna_xs2", "falcon_h1_34b", "nemotron3_super_120b",
        "zaya1_8b_2rows", "ouro_2_6b"])
def test_paged_gqa_walk_compiles_for_v5e(v5e_chip, q_shape, pool, bucket,
                                         tiles):
    """The matrix-unit decode kernel that walks a list of page blocks
    (PR 50) alone, at the four grouped-query cells' shapes and tables and
    at the looped cell's (PR 55: one head of 128 a KV head, pages of 16,
    under the name it had): the gate says yes,
    the tile follows from the heads a KV head, and Mosaic takes it (the
    unaligned windows of sublanes of 6 heads a KV head among the rest) with
    the pools left in HBM; a table one block wide keeps the grid."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    _, ps, width = pool
    nkv = width // q_shape[2]
    assert ppa.walk_supported(q_shape, pool, "bfloat16", bucket)
    assert not ppa.walk_supported(q_shape, pool, "bfloat16", 4)
    assert ppa.tile_rows(q_shape[1] // nkv) == tiles
    sh = SingleDeviceSharding(v5e_chip)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=sh) for shape, dt in (
        (q_shape, jnp.float32), (pool, "bfloat16"), (pool, "bfloat16"),
        ((q_shape[0], bucket), jnp.int32), ((q_shape[0],), jnp.int32))]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda *a: ppa.paged_decode_attention(
            *a, sm_scale=0.088)).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    assert ("paged_decode_attention_gqa" in text) == (nkv < q_shape[1])
    assert pool_sized_copies(text, pool[0] * ps * width) == []


@pytest.mark.parametrize("q_shape,pool,group", [
    ((128, 64, 128), (5 * 2304, 128, 128), 48),
    ((64, 16, 64), (6 * 1792, 64, 128), 96),
], ids=["deepseek_v32_exp", "keye_vl2_30b_a3b"])
def test_paged_indexer_blocks_fit_their_vmem_budget_on_v5e(
        v5e_chip, q_shape, pool, group):
    """The paged indexer kernel alone at the two serving cells' sizes (128
    rows of 64 heads of 128 over 5 x 2,304 pages; 64 rows of 16 heads of 64
    over 6 x 1,792; 288-page tables of 128 bfloat16 tokens): a grid step
    covers 48 pages of 32 KB or 96 of 16 KB, a block stays under
    `BLOCK_BYTES` (the kernel holds two), the whole page table (36,864
    entries at 128 rows, 147 KB) fits the chip's scalar memory beside the
    lengths, and Mosaic takes it with the pool left in HBM and uncopied."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_kernels import paged_indexer as pi

    (B, J, D), (_, _, ps), P = q_shape, pool, 288
    assert pi.paged_indexer_supported(q_shape, pool, jnp.bfloat16)
    assert pi.pages_per_grid_step(P, D * ps * 2) == group
    assert group * D * ps * 2 <= pi.BLOCK_BYTES and B * P <= pi.TABLE_ENTRIES
    sh = SingleDeviceSharding(v5e_chip)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=sh) for shape, dt in (
        (q_shape, jnp.float32), ((B, J), jnp.float32), (pool, jnp.bfloat16),
        ((B, P), jnp.int32), ((B,), jnp.int32))]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda *a: pi._call(*a, False)).lower(
            *args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert kernel_calls(text, "paged_indexer_scores") == 1
    assert pool_sized_copies(text, pool[0] * D * ps) == []
