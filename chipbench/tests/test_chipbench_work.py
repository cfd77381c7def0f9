"""Needed FLOPs and bytes against numbers worked by hand at the cells'
shapes, and the peak table."""

import pytest

from chipbench import spec, work


def dims(config):
    c = spec.load_cell({"granite-3-2b": "granite-3-2b.decode",
                        "chatglm3-6b": "chatglm3-6b.decode"}[config])
    return spec.reference(c.config["reference"]).dims(c.config)


# granite-3-2b: d 2048, 40 layers, 32 heads / 8 KV heads of 64, d_ff 8192,
# vocab 49155, tied. One layer: Q and K, V and O, gate, up and down.
G_LAYER = 2048 * (2048 + 2 * 512) + 2048 * 2048 + 3 * 2048 * 8192
G_HEAD = 2048 * 49155
G_KV_TOKEN = 40 * 2 * 8 * 64 * 2            # bytes of K and V, all layers
# chatglm3-6b: d 4096, 28 layers, 32 heads / 2 KV groups of 128,
# d_ff 13696, vocab 65024, untied.
C_LAYER = 4096 * (4096 + 2 * 256) + 4096 * 4096 + 3 * 4096 * 13696
C_HEAD = 4096 * 65024
C_KV_TOKEN = 28 * 2 * 2 * 128 * 2


def test_layer_sizes_by_hand():
    assert G_LAYER == 60_817_408
    assert C_LAYER == 203_948_032
    assert work.layer_params(dims("granite-3-2b")) == G_LAYER
    assert work.layer_params(dims("chatglm3-6b")) == C_LAYER
    assert work.kv_bytes_per_token(dims("granite-3-2b")) == 81_920
    assert work.kv_bytes_per_token(dims("chatglm3-6b")) == 28_672


def test_granite_prefill_b4_p2048():
    w = work.prefill_work(dims("granite-3-2b"), 4, 2048)
    linear = 2 * (4 * 2048) * 40 * G_LAYER
    attn = 4 * 4 * 32 * 64 * (2048 * 2048 / 2) * 40
    head = 2 * 4 * G_HEAD
    assert w.flops == pytest.approx(linear + attn + head, rel=1e-12)
    # 39,857,296,506,880 + 2,748,779,069,440 + 805,355,520
    assert w.flops == 42_606_880_931_840
    weights = (40 * G_LAYER + G_HEAD) * 2
    assert w.bytes == weights + 4 * 2048 * 2048 * 2 + 4 * 2048 * G_KV_TOKEN


def test_granite_decode_b64_p128():
    d = dims("granite-3-2b")
    pre = work.prefill_work(d, 64, 128)
    assert pre.flops == pytest.approx(
        2 * 8192 * 40 * G_LAYER + 4 * 64 * 32 * 64 * 8192 * 40
        + 2 * 64 * G_HEAD, rel=1e-12)
    step = work.decode_step_work(d, 64, 128)       # first step: 128 filled
    assert step.flops == pytest.approx(
        2 * 64 * 40 * G_LAYER + 4 * 64 * 32 * 64 * 129 * 40
        + 2 * 64 * G_HEAD, rel=1e-12)
    assert step.bytes == ((40 * G_LAYER + G_HEAD) * 2 + 64 * 2048 * 2
                          + 64 * 129 * G_KV_TOKEN)
    rnd = work.decode_round_work(d, 64, 128, 384)
    # the filled positions run 128 .. 511, so keys attended 129 .. 512
    keys = sum(range(129, 513))
    assert rnd.bytes == (384 * ((40 * G_LAYER + G_HEAD) * 2 + 64 * 2048 * 2)
                         + 64 * keys * G_KV_TOKEN)


def test_chatglm_decode_b32_p256():
    d = dims("chatglm3-6b")
    step = work.decode_step_work(d, 32, 256)
    weights = (28 * C_LAYER + C_HEAD) * 2
    assert weights == 11_953_766_400
    assert step.bytes == weights + 32 * 4096 * 2 + 32 * 257 * C_KV_TOKEN
    assert step.flops == pytest.approx(
        2 * 32 * 28 * C_LAYER + 4 * 32 * 32 * 128 * 257 * 28
        + 2 * 32 * C_HEAD, rel=1e-12)
    pre = work.prefill_work(d, 32, 256)
    assert pre.flops == pytest.approx(
        2 * 8192 * 28 * C_LAYER + 4 * 32 * 32 * 128 * (256 * 256 / 2) * 28
        + 2 * 32 * C_HEAD, rel=1e-12)


def test_roofline_picks_the_binding_peak():
    peaks = work.load_peaks("TPU v5 lite")
    assert peaks["bf16_flop_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    d = dims("chatglm3-6b")
    t, bound = work.roofline_s(work.decode_step_work(d, 32, 256), peaks)
    assert bound == "memory"
    assert t == pytest.approx(
        (11_953_766_400 + 32 * 4096 * 2 + 32 * 257 * C_KV_TOKEN) / 819e9)
    t, bound = work.roofline_s(work.prefill_work(d, 32, 256), peaks)
    assert bound == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peaks"):
        work.load_peaks("TPU v9 imaginary")
