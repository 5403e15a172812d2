"""A/B: BERT bench step with use_flash_attention True vs False.

Timing rides tools/_timing.py (the shared warmup + windowed protocol) so
this harness, _rn_igemm.py and tools/tune.py all report comparable numbers.
"""
import sys
sys.path.insert(0, "/root/repo")
import numpy as np  # noqa: E402

from tools import _timing  # noqa: E402


def run(use_flash):
    import paddle_tpu as pt
    from paddle_tpu import compile_cache
    from paddle_tpu.models import transformer

    compile_cache.configure()
    cfg = transformer.TransformerConfig(
        vocab_size=30522, hidden_size=768, num_layers=12, num_heads=12,
        ffn_size=3072, max_position=512, dropout=0.0, use_tp=False,
        use_flash_attention=use_flash)
    batch, seq_len, iters = 128, 128, 50
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        avg_loss, _ = transformer.bert_pretrain(cfg, seq_len=seq_len)
        opt = pt.contrib.mixed_precision.decorate(pt.optimizer.Adam(learning_rate=1e-4))
        opt.minimize(avg_loss)
    from __graft_entry__ import _example_feed
    feed = _example_feed(cfg, batch, seq_len)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.run(main_p, feed=feed, fetch_list=[avg_loss])  # compile both sigs
        m = _timing.measure(
            lambda: exe.run(main_p, feed=feed),
            lambda: pt.global_scope().find_var("lm_head.b"),
            iters=iters, passes=2, warmup=1)
        (loss,) = exe.run(main_p, feed=feed, fetch_list=[avg_loss])
        assert np.isfinite(float(np.asarray(loss)))
        from paddle_tpu import tuning
        from paddle_tpu.tuning.learned import store as learned_store
        if learned_store.recording_enabled(tool=True):
            learned_store.record(
                "ab.bert", f"workload=bert b={batch} s={seq_len}", "-",
                tuning.device_kind(), f"flash{int(bool(use_flash))}",
                windows_s=m["windows_s"], median_s=m["median_s"],
                min_s=m["min_s"], band=m["band"], source="ab")
    dt = m["median_s"]
    tokens = batch * seq_len
    H, L_, F, V = 768, 12, 3072, 30522
    n_params = L_ * (4 * H * H + 2 * H * F) + H * V
    step_flops = 6 * n_params * tokens + 12 * L_ * H * seq_len * tokens
    mfu = (step_flops / dt) / 197e12
    print(f"use_flash={use_flash}: {dt*1e3:.1f} ms/step (band "
          f"{m['band']:.3f}), {tokens/dt:,.0f} tok/s, MFU {mfu*100:.1f}%",
          flush=True)


run(sys.argv[1] == "1")
