"""The benchmark's FLOP and byte counts against hand-worked values, and
its table of peaks."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import counts as C  # noqa: E402

SMALL = dict(n_layers=2, d_model=4, n_heads=2, n_kv_heads=2, head_dim=2,
             d_ff=8, vocab_size=10)


def test_vertical_learner_flops():
    small = dict(n_workers=2, input_dim=4, encoder_dims=[3], embed_dim=2,
                 head_dims=[5], n_classes=3)
    # encoder 2*(4*3 + 3*2) = 36 per worker, head 2*(2*5 + 5*3) = 50
    assert C.vertical_fwd_flops(**small) == 2 * 36 + 50
    assert C.vertical_train_flops(**small) == 3 * 122
    paper = dict(n_workers=4, input_dim=256, encoder_dims=[256, 128],
                 embed_dim=64, head_dims=[512, 512, 512], n_classes=10)
    assert C.vertical_train_flops(**paper) == 5_928_960


def test_decoder_flops_per_token_and_prefill():
    # weights 2 layers * (4*2*(2*2 + 2*2) + 4*8*3) = 320; attention
    # 2 layers * 4 * context 3 * 2 heads * 2; LM head 2 * 4 * 10
    assert C.decoder_matmul_params(**{k: v for k, v in SMALL.items()
                                      if k != "vocab_size"}) == 320
    assert C.decoder_token_flops(3, **SMALL) == 640 + 96 + 80
    assert C.decoder_token_flops(3, lm_head=False, **SMALL) == 736
    # positions 1 and 2 without heads, then one LM head
    assert C.prefill_flops(2, **SMALL) == (640 + 32) + (640 + 64) + 80


def test_contention_work_from_its_five_numbers():
    w = C.contention_work(n=4, k=8, bits=8, idb=2, max_rounds=3)
    # sensing 3 rounds * 10 slots * 4 * 8 = 960 bits; words 4*8*10 bits;
    # winners 8 * 2 bits; two 3-long int32 rows
    assert w == {"ops": 960, "bytes": (320 + 960) / 8 + 16 / 8 + 24}
    assert C.id_bits(16) == 4 and C.id_bits(4) == 2 and C.id_bits(1) == 1


def test_roofline_share_names_its_bound():
    peak = {"int8_ops_per_s": 1e14, "hbm_bytes_per_s": 1e11}
    assert C.roofline_share(1e12, 1e8, 0.02, peak, "int8_ops_per_s") == {
        "share_pct": 50.0, "bound": "compute"}
    assert C.roofline_share(1e10, 1e9, 0.1, peak, "int8_ops_per_s") == {
        "share_pct": 10.0, "bound": "memory"}


def test_peaks_table_is_keyed_by_device_kind():
    v5e = C.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        C.peaks("TPU v99")
