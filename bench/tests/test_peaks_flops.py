"""The peaks table and each configuration's model-FLOP function."""
import json
import os

import pytest

from harness.peaks import peaks_for
from harness.spec import BENCH_DIR, load_module


def _flops(name):
    return load_module(os.path.join(BENCH_DIR, "flops", f"{name}.py"),
                       f"flops_{name}")


def _sizes(name):
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)["sizes"]


def test_v5e_peak_is_the_published_one():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("TPU v9 imaginary")


def test_lstm_paper_flops_by_hand():
    # forward per token: gates [x(64), h(256)] @ (320, 1024) and head
    # (256, 96), two FLOPs a weight; backward twice the forward
    forward = 2 * 320 * 1024 + 2 * 256 * 96
    assert forward == 704512
    assert _flops("lstm-paper").flops_per_token(_sizes("lstm-paper")) \
        == 3 * forward == 2113536


def test_mamba2_flops_are_6nd_plus_the_ssd_term():
    """The system's own count (``configs.base.model_flops``) is 6 N D over
    the weights, embedding included (tied: it is the head's matmul).  It
    folds SSD's mixing into nothing: the benchmark adds the chunked SSD
    term, three times its forward count per layer."""
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec, model_flops

    f = _flops("mamba2-370m")
    c = _sizes("mamba2-370m")
    cfg = get_config("mamba2-370m")
    assert cfg.ssm.chunk == c["chunk_size"]
    B, T = 2, 2048
    six_nd = model_flops(cfg, ShapeSpec("t", T, B, "train"))
    ssd = 3 * c["n_layer"] * f.ssd_flops_per_token(c) * B * T
    assert f.flops_per_token(c) * B * T == six_nd + ssd
    # chunk 128, d_state 128, one group, 32 heads of 64: causal half of
    # the intra-chunk part plus the two state terms, per token per layer
    assert f.ssd_flops_per_token(c) == 128 * 128 + 128 * 32 * 64 \
        + 4 * 128 * 32 * 64
    assert f.flops_per_token(c) == 2399551488
