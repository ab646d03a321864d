"""Work counts against sums written out from the papers' tables."""

import json
from pathlib import Path

import pytest

import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def mbconv_macs(res_in, c_in, c_mid, c_out, k, s, c_se):
    """Multiply-adds of one block at input side ``res_in``."""
    res_out = res_in // s
    macs = res_out * res_out * (k * k * c_mid + c_mid * c_out) + 2 * c_mid * c_se
    if c_mid != c_in:
        macs += res_in * res_in * c_in * c_mid
    return macs


# EfficientNet-B0, arXiv:1905.11946 Table 1, at 224: (input side of the
# stage, expand, k, first stride, c_in, c_out, repeats)
B0_TABLE = [
    (112, 1, 3, 1, 32, 16, 1),
    (112, 6, 3, 2, 16, 24, 2),
    (56, 6, 5, 2, 24, 40, 2),
    (28, 6, 3, 2, 40, 80, 3),
    (14, 6, 5, 1, 80, 112, 3),
    (14, 6, 5, 2, 112, 192, 4),
    (7, 6, 3, 1, 192, 320, 1),
]

# MobileNet-V3-Large, arXiv:1905.02244 Table 1, at 224: (input side,
# c_in, expanded, c_out, k, s, SE squeeze).  The squeeze is the paper's
# code: expanded / 4 rounded to a multiple of 8 (18 -> 24, 30 -> 32).
V3L_TABLE = [
    (112, 16, 16, 16, 3, 1, 0), (112, 16, 64, 24, 3, 2, 0),
    (56, 24, 72, 24, 3, 1, 0), (56, 24, 72, 40, 5, 2, 24),
    (28, 40, 120, 40, 5, 1, 32), (28, 40, 120, 40, 5, 1, 32),
    (28, 40, 240, 80, 3, 2, 0), (14, 80, 200, 80, 3, 1, 0),
    (14, 80, 184, 80, 3, 1, 0), (14, 80, 184, 80, 3, 1, 0),
    (14, 80, 480, 112, 3, 1, 120), (14, 112, 672, 112, 3, 1, 168),
    (14, 112, 672, 160, 5, 2, 168), (7, 160, 960, 160, 5, 1, 240),
    (7, 160, 960, 160, 5, 1, 240),
]


def b0_macs():
    total = 112 * 112 * 27 * 32                       # stem
    for res, e, k, s, c_in, c_out, reps in B0_TABLE:
        for i in range(reps):
            ci = c_in if i == 0 else c_out
            total += mbconv_macs(res if i == 0 else res // s, ci, ci * e,
                                 c_out, k, s if i == 0 else 1, ci // 4)
    return total + 7 * 7 * 320 * 1280 + 1280 * 1000    # head, classifier


def v3l_macs():
    total = 112 * 112 * 27 * 16
    for res, ci, cm, co, k, s, c_se in V3L_TABLE:
        total += mbconv_macs(res, ci, cm, co, k, s, c_se)
    return total + 7 * 7 * 160 * 960 + 960 * 1280 + 1280 * 1000


@pytest.mark.parametrize("name,macs,published", [
    ("efficientnet_b0", b0_macs, 0.39e9),
    ("mobilenet_v3_large", v3l_macs, 219e6),
])
def test_model_flops_match_table_sums(name, macs, published):
    flops = work.model_flops_per_image(load(name), 224)
    assert flops == 2 * macs()
    # and the papers' own totals, which also count batch norm, to a few
    # percent
    assert abs(flops / 2 - published) / published < 0.03


def test_block_bytes_are_input_weights_output_once():
    cfg = load("efficientnet_b0")
    first = work.block_work(cfg, 224)[0]
    # block 0: 112x112x32 in, 112x112x16 out; dw 3x3x32, proj 32x16,
    # SE 32x8 + 8 + 8x32 + 32 (no expand: ratio 1)
    assert first.act_bytes_per_image == 4 * (112 * 112 * 32 + 112 * 112 * 16)
    assert first.weight_bytes == 4 * (9 * 32 + 32 * 16 + 2 * 32 * 8 + 8 + 32)
    assert first.bytes(3) == 3 * first.act_bytes_per_image + first.weight_bytes


def test_roofline_bound_is_the_larger_of_compute_and_memory():
    lw = work.LayerWork("x", flops_per_image=2e9, act_bytes_per_image=1e6,
                        weight_bytes=0.0)
    assert lw.roofline_s(1, 1e12, 1e12) == pytest.approx(2e-3)
    assert lw.roofline_s(1, 1e15, 1e6) == pytest.approx(1.0)
