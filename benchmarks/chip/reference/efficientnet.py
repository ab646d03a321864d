"""Plain reference of EfficientNet-B0 (Tan & Le 2019, arXiv:1905.11946,
Table 1): stem 3x3/2 conv -> silu -> MBConv blocks (SE with silu squeeze
and sigmoid gate, squeeze width = block input channels x ``se_ratio``) ->
1x1 head conv -> silu -> global mean pool -> classifier.

Convolutions carry no bias and no batch norm: with random weights a
folded batch norm is one more per-channel scale, and the served entry
takes none.  Everything else is the published network at the widths of
the configuration file.
"""

from __future__ import annotations

from typing import Dict, List

from .common import Block, act, block_leaves, chain, conv, dense, \
    pointwise, round_filters


def blocks(cfg: dict) -> List[Block]:
    wm = cfg["width_mult"]
    out: List[Block] = []
    c_in = round_filters(cfg["stem_c"], wm)
    for expand, k, s, c_out, repeats in cfg["stages"]:
        c_out = round_filters(c_out, wm)
        for i in range(repeats):
            out.append(Block(
                c_in=c_in, c_mid=c_in * expand, c_out=c_out, k=k,
                s=s if i == 0 else 1,
                c_se=max(1, int(c_in * cfg["se_ratio"])),
                act="silu", se_act="silu", gate_act="sigmoid"))
            c_in = c_out
    return out


def leaves(cfg: dict) -> Dict[str, object]:
    bl = blocks(cfg)
    wm = cfg["width_mult"]
    stem_c = round_filters(cfg["stem_c"], wm)
    head_c = round_filters(cfg["head_c"], wm)
    tree: Dict[str, object] = {
        "stem": ((3, 3, 3, stem_c), "normal", 1.0),
        "head": ((bl[-1].c_out, head_c), "normal", 2.0),
        "cls_w": ((head_c, cfg["num_classes"]), "normal", 1.0),
        "cls_b": ((cfg["num_classes"],), "zeros", 0.0),
    }
    for i, b in enumerate(bl):
        tree[f"block{i}"] = block_leaves(b)
    return tree


def head_layers(cfg: dict):
    """(c_in, c_out) of the dense layers after the pool."""
    head_c = round_filters(cfg["head_c"], cfg["width_mult"])
    return [(head_c, cfg["num_classes"])]


def forward(params: dict, images, cfg: dict, passes=None):
    """(B, H, W, 3) images -> (B, num_classes) logits, in the dtype of
    ``params`` and ``images`` (``passes``: see ``common.contract``)."""
    x = act(conv(images, params["stem"], 2, passes), "silu")
    x = chain(x, params, blocks(cfg), passes)
    x = act(pointwise(x, params["head"], passes), "silu")
    x = x.mean(axis=(1, 2))
    return dense(x, params["cls_w"], passes) + params["cls_b"]


