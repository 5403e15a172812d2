"""Planted faults of the training decoder (`tools/decoder_faults.py`), each
caught by what `tests/test_decoder_moe_train.py` and the benchmark's runner
compare: the losses of the first steps, the update's direction and length,
or a leaf's gradient."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.models import decoder_moe as dm  # noqa: E402
from tools import decoder_faults as df  # noqa: E402

CFG = dm.DecoderMoEConfig(
    layer_types=(dm.SLIDING, dm.FULL),
    yarn=(16.0, 16, 32.0, 1.0, 1.2772588722239782))
# which of the clean run's limits a fault passes (measured: loss gap 2e-2 to
# 1.5e-1 against 1e-6, 1 - cosine 0.06 to 0.32 against 4e-10, the worst
# leaf 0.5 to 1.2 against 1e-6). The router's gradient alone moves no loss
# of the first step and little of the whole update
CAUGHT_BY = {"window_ignored": ("loss", "cosine", "leaf"),
             "window_off_by_one": ("loss", "cosine", "leaf"),
             "yarn_on_sliding": ("loss", "cosine", "leaf"),
             "not_renormalised": ("loss", "cosine", "leaf"),
             "last_expert_dropped": ("loss", "cosine", "leaf"),
             "router_grad_cut": ("leaf",),
             "capacity": ("loss", "cosine", "leaf")}


@pytest.fixture(scope="module")
def reference():
    return df.agreement(CFG)["reference"]


@pytest.mark.parametrize("fault", sorted(df.FAULTS))
def test_a_planted_fault_is_caught(reference, fault):
    with df.FAULTS[fault]():
        got = df.agreement(CFG, reference=reference)
    over = {"loss": got["loss_gap"] > 1e-3,
            "cosine": got["update_cosine"] < 0.99,
            "leaf": max(got["grad_rel"].values()) > 0.1}
    assert {k for k, v in over.items() if v} >= set(CAUGHT_BY[fault]), got
    router = got["grad_rel"]["decoder.layer1.moe.router"]
    assert (router > 0.99) == (fault == "router_grad_cut")
    assert (got["counters"]["train.moe.dropped"] > 0) == (fault == "capacity")
