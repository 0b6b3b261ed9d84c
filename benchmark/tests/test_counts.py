import pytest

from benchmark.harness import counts, peaks, spec


def test_one_transformer_layer_by_hand():
    # d=1024, ff=4096, T=1024, forward, one sequence:
    # QKV+O: 4 * 1024*1024 weights, MLP: 2 * 1024*4096 -> 12,582,912
    # weights, 2 FLOPs each per token: 25,165,824 * 1024 tokens
    # attention: QK^T and PV, 2 * (2 * 1024*1024*1024), causal half
    want = 25_165_824 * 1024 + 2 * 2 * 1024 ** 3 // 2
    assert counts.transformer_layer_fwd_flops(1024, 4096, 1024) == want


def test_lm_train_flops_per_token():
    cfg = {"n_embd": 1024, "n_layer": 24, "vocab_size": 50257}
    f = spec.load_module("counts", "transformer_lm").train_flops(
        cfg, {"inputs": {"seq_len": 1024}}) / 1024
    # 6 * (24 * 12 * 1024^2 + 1024 * 50257) + 24 * 6 * 2*1024*1024 / 2
    want = 6 * (24 * 12 * 1024 ** 2 + 1024 * 50257) + 24 * 6 * 1024 ** 2
    assert f == pytest.approx(want)
    assert f == pytest.approx(2.2717e9, rel=1e-4)


def test_one_bottleneck_block_by_hand():
    # conv2_x first block at 56x56: 1x1 64->64, 3x3 64->64, 1x1 64->256
    # and a 1x1 64->256 projection; multiply-adds per output position
    macs = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert counts.bottleneck_fwd_flops(56, 56, 64, 64, 256, 1,
                                       True) == 2 * macs
    # He et al. count 3.8e9 multiply-adds for the 50-layer net
    resnet50 = spec.load_module("counts", "resnet50")
    assert resnet50.fwd_flops() / 2 == pytest.approx(3.86e9, rel=0.01)


def test_flash_counts_and_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    f, b = counts.flash_fwd(64, 1024, 64, 4)
    assert f == 2 * 2 * 64 * 1024 * 1024 * 64 // 2
    assert b == 4 * 64 * 1024 * 64 * 4
    sec, which = counts.roofline_seconds(f, b, pk)
    assert which == "memory" and sec == pytest.approx(b / 819e9)
    fb, bb = counts.flash_bwd(64, 1024, 64, 4)
    assert fb == 2.5 * f and bb == 2 * b


def test_share_above_105_is_an_error_not_clipped():
    assert counts.share_pct(0.5, 1.0, "x") == 50.0
    assert counts.share_pct(1.04, 1.0, "x") == pytest.approx(104.0)
    with pytest.raises(counts.CountError):
        counts.share_pct(1.06, 1.0, "x")
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
