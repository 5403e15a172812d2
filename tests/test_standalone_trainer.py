"""Native standalone trainer (reference train/demo/demo_trainer.cc role):
a C binary hosting the runtime in-process loads a saved train model, trains
from a MultiSlot data file, and writes back persistables — no user Python."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BUILD = os.path.join(_REPO, "tools", "build_standalone_trainer.sh")


def _build(out_path):
    """Always build from the committed .c: a binary left in the tree by an
    earlier run cannot be told from a stale one (mtimes do not survive a
    copy or a checkout)."""
    r = subprocess.run(["bash", _BUILD, out_path], capture_output=True)
    return r.returncode == 0


def test_save_load_train_model_roundtrip(tmp_path):
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 3
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            x = L.data(name="x", shape=[4], dtype="float32")
            y = L.data(name="y", shape=[1], dtype="float32")
            loss = L.mean(L.square_error_cost(L.fc(x, size=1), y))
            pt.optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        pt.io.save_train_model(str(tmp_path), [x, y], loss, exe, main,
                               startup)
        w = np.asarray(pt.global_scope().find_var("fc_0.w_0"))
    scope2 = pt.Scope()
    with pt.scope_guard(scope2):
        main2, startup2, meta = pt.io.load_train_model(str(tmp_path), exe)
    assert meta["feed_names"] == ["x", "y"]
    assert meta["loss_name"] == loss.name
    # optimizer ops survived the round trip (it is a TRAIN program)
    assert any(op.type == "sgd" for op in main2.global_block.ops)
    np.testing.assert_array_equal(
        np.asarray(scope2.find_var("fc_0.w_0")), w)


@pytest.mark.skipif(shutil.which("cc") is None, reason="no C compiler")
def test_standalone_trainer_binary_trains(tmp_path):
    binary = str(tmp_path / "standalone_trainer")
    if not _build(binary):
        pytest.skip("standalone trainer build failed (no python3-config?)")
    # build + save a CTR train model
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 11
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            ids = L.data(name="ids", shape=[4], dtype="int64")
            dense = L.data(name="dense", shape=[3], dtype="float32")
            label = L.data(name="label", shape=[1], dtype="float32")
            emb = L.embedding(ids, size=[50, 8])
            feat = L.concat([L.reshape(emb, [-1, 32]), dense], axis=1)
            h = L.fc(feat, size=16, act="relu")
            logit = L.fc(h, size=1)
            loss = L.mean(
                L.sigmoid_cross_entropy_with_logits(logit, label))
            pt.optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    model_dir = str(tmp_path / "model")
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        pt.io.save_train_model(model_dir, [ids, dense, label], loss, exe,
                               main, startup)
        w0 = np.asarray(pt.global_scope().find_var("fc_0.w_0")).copy()

    rng = np.random.default_rng(0)
    data = str(tmp_path / "data.txt")
    with open(data, "w") as f:
        for _ in range(320):
            i4 = rng.integers(0, 50, 4)
            d3 = rng.random(3).round(4)
            yv = int(i4.sum() % 2)
            f.write(f"4 {' '.join(map(str, i4))} "
                    f"3 {' '.join(map(str, d3))} 1 {yv}\n")

    out_dir = str(tmp_path / "out")
    env = dict(os.environ)
    env["PADDLE_TPU_HOME"] = _REPO
    r = subprocess.run([binary, model_dir, data, "32", "2", out_dir],
                       env=env, capture_output=True, timeout=240)
    assert r.returncode == 0, (r.stdout.decode()[-2000:]
                               + r.stderr.decode()[-2000:])
    assert b"saved to" in r.stdout

    # the binary's training moved the parameters it saved
    scope2 = pt.Scope()
    with pt.scope_guard(scope2):
        pt.io.load_vars(exe, out_dir, main,
                        vars=[v for v in main.list_vars()
                              if getattr(v, "persistable", False)])
        w1 = np.asarray(scope2.find_var("fc_0.w_0"))
    assert not np.allclose(w0, w1), "standalone trainer moved no parameters"
