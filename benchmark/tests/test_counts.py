"""The operation and byte functions against hand-worked numbers."""
import pytest

from benchmark import counts, peaks, traffic


def test_bert_base_s512_flops_per_token():
    # one layer, one token: QKV 2*768*2304, proj 2*768*768,
    # FFN 2*2*768*3072 = 3,538,944 + 1,179,648 + 9,437,184
    layer = 3_538_944 + 1_179_648 + 9_437_184
    attn = 4 * 512 * 768                       # QK^T and PV over 512 keys
    head = 0.1125 * 2 * 768 * 30522
    want = 3 * (12 * (layer + attn) + head)
    got = counts.bert_train_flops_per_token(
        hidden=768, intermediate=3072, layers=12, vocab=30522, seq=512,
        label_share=0.1125)
    assert got == pytest.approx(want)
    assert got == pytest.approx(582.1e6, rel=1e-3)


def test_label_share_of_the_padded_mix():
    spec = traffic.load("s512_b32_padded")
    share = traffic.train_label_share(spec, 32)
    # lengths evenly 256..512 average 384 of 512; 15 % of them labelled
    assert share == pytest.approx(0.15 * 384 / 512, rel=0.01)


def test_flash_needs_six_matmuls_and_twelve_tensors():
    flops, nbytes = counts.flash_train_flops_bytes(
        batch=32, heads=12, seq=512, head_dim=64, layers=12, causal=False)
    one = 2 * 32 * 12 * 512 * 512 * 64
    assert flops == 12 * 6 * one == pytest.approx(927.7e9, rel=1e-3)
    assert nbytes == 12 * 12 * 32 * 512 * 768 * 2
    least, bound = counts.roofline_seconds(
        flops, nbytes, peaks.device_peaks("TPU v5 lite"))
    assert bound == "flops" and least == pytest.approx(4.709e-3, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(RuntimeError):
        peaks.device_peaks("TPU v9 imaginary")
