"""chip_smoke.py's two phases at tiny sizes on the CPU, so the script cannot
rot between chip runs — plus the refusal: chip_smoke.main exits non-zero
off the chip and prints no result line."""
import types

import pytest

import chip_smoke
from paddle_tpu.models import transformer
from paddle_tpu.ops.pallas_kernels import paged_attention as ppa
from paddle_tpu.pipeline import jit_compile_counter
from paddle_tpu.serving import DecoderConfig, decoder_tiny


def test_trainer_phase_tiny_single_and_dp():
    cfg = transformer.bert_tiny(use_tp=False)
    out = chip_smoke.trainer_phase(cfg, batch=8, seq_len=32, steps=4)
    assert out["losses"][-1] < out["losses"][0]
    assert out["attention"] == {"backend": "xla", "recheck_held": True,
                                "traces": out["attention"]["traces"]}
    assert out["param_platform"] == "cpu"
    # the dp arm on the virtual CPU mesh: the feed-split and the
    # parameter-placement checks run (the CPU backend has no memory stats)
    with jit_compile_counter() as compiles:
        dp = chip_smoke.trainer_phase(cfg, batch=4, seq_len=32, steps=3,
                                      dp=4)
    assert dp["losses"][-1] < dp["losses"][0]
    assert "bytes_in_use_grown" not in dp
    # startup + ONE compile of the step: neither the step's own
    # mesh-resident outputs coming back as inputs nor the switch from a
    # host batch to a staged one may trace it again
    assert compiles.count == 2, compiles.events


def test_server_phase_tiny_runs_the_paged_kernel(monkeypatch):
    # interpret mode makes the Pallas paged kernel runnable here, so the
    # dispatch picks it exactly as it does on the chip
    monkeypatch.setattr(ppa, "INTERPRET", True)
    # the smallest geometry the kernel takes: two heads of 64 fill one
    # 128-lane row of the pool, a page is one sublane tile
    cfg = DecoderConfig(vocab_size=97, hidden_size=128, num_layers=2,
                        num_heads=2, ffn_size=64, max_position=64)
    out = chip_smoke.server_phase(cfg, page_size=8, pool_pages=32,
                                  prompt_lens=(9, 3, 17), max_new=4)
    assert out["paged_attention"]["backend"] == "pallas_paged"
    assert out["tokens"] == 12 and out["leaked_pages"] == 0
    assert out["oracle_worst_logit_gap"] <= chip_smoke.ORACLE_LOGIT_TOL


def test_server_phase_fails_when_the_reference_runs_for_the_kernel(
        monkeypatch):
    """The backend that ran must be the one the dispatch rule names: a
    decision that names the kernel while the reference runs fails."""
    from paddle_tpu.ops import attention_ops

    monkeypatch.setattr(
        attention_ops, "paged_attention_backend",
        lambda *a, **k: ("pallas_paged", "analytic"))
    with pytest.raises(chip_smoke.SmokeFailure, match="chose 'pallas_paged'"):
        chip_smoke.server_phase(decoder_tiny(), page_size=4, pool_pages=64,
                                prompt_lens=(9, 3), max_new=2)


def test_main_refuses_without_a_chip(capsys):
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == ""      # no result line off the chip


def test_result_line_is_ok_and_device_alone(monkeypatch, capsys):
    """The driver reads the last stdout line: one JSON object with exactly
    the keys ok and device {platform, kind, count}; details go before it."""
    import json

    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [chip])
    monkeypatch.setattr(chip_smoke.compile_cache, "configure", lambda: "x")
    monkeypatch.setattr(chip_smoke, "trainer_phase",
                        lambda *a, **k: {"param_platform": "tpu"})
    monkeypatch.setattr(chip_smoke, "server_phase", lambda *a, **k: {})
    monkeypatch.setattr(chip_smoke, "pool_layout_phase", lambda *a, **k: {})
    assert chip_smoke.main() == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
