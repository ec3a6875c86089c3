"""Spread, FLOP, byte and roofline arithmetic against hand-worked values;
traffic reproducible from the seed."""

import numpy as np
import pytest

from lib import flops, peaks, stats, traffic as tg


def test_iqr_spread_is_statistics_quantiles():
    # quantiles([1..6], n=4) = 1.75, 3.5, 5.25
    assert stats.iqr_spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)


def test_bert_large_flops_per_sequence():
    cfg = {"hidden_size": 1024, "intermediate_size": 4096, "num_hidden_layers": 24,
           "vocab_size": 30522}
    # 24 * (4*1024^2 + 2*1024*4096) + 1024^2 + 30522*1024
    assert flops.bert_matmul_params(cfg) == 24 * 12582912 + 1048576 + 31254528
    dense = 6 * 334292992 * 512
    attn = 12 * 24 * 512 * 512 * 1024
    assert flops.bert_train_flops_per_seq(cfg, 512) == pytest.approx(dense + attn)
    assert 1.0e12 < dense + attn < 1.2e12


def test_flash_and_adam_work():
    fl, by = flops.flash_train_flops_bytes(batch=8, heads=16, seq=512, head_dim=64, layers=24)
    assert fl == pytest.approx(9 * 2 * 512 * 512 * 64 * 8 * 16 * 24)
    assert by == pytest.approx(17 * 8 * 16 * 512 * 64 * 2 * 24)
    assert flops.adam_bytes(1000, grad_bytes=4) == 1000 * 30


def test_peaks_table_and_roofline():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    mem = peaks.roofline_share(flops=1e9, bytes_moved=819e6, seconds=2e-3, peaks=v5e)
    assert mem["bound"] == "memory" and mem["share_pct"] == pytest.approx(50.0)
    cmp_ = peaks.roofline_share(flops=197e9, bytes_moved=1e3, seconds=4e-3, peaks=v5e)
    assert cmp_["bound"] == "compute" and cmp_["share_pct"] == pytest.approx(25.0)


def test_training_batches_from_seed():
    p = {"seq_len": 32, "mask_prob": 0.15, "mask_token_id": 103, "first_token_id": 1000}
    a = tg.mlm_nsp_batch(p, 2**31 + 1, 4, 8, 30522)
    b = tg.mlm_nsp_batch(p, 2**31 + 1, 4, 8, 30522)
    c = tg.mlm_nsp_batch(p, 2**31 + 1, 5, 8, 30522)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()
    ids, labels, nsp = a
    assert ids.shape == labels.shape == (8, 32) and nsp.shape == (8,)
    assert len({tuple(r) for r in ids}) == 8                # rows all differ
    assert ((labels != -100).sum(axis=1) >= 1).all()
    assert set(np.unique(nsp)) <= {0, 1}
