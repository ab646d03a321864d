"""Plain reference of MobileNet-V3-Large (Howard et al. 2019,
arXiv:1905.02244, Table 1): stem 3x3/2 conv -> hard-swish -> 15
bottlenecks (relu or hard-swish per row, SE on the rows Table 1 marks,
with a relu squeeze and a hard-sigmoid gate) -> 1x1 conv to 960 ->
hard-swish -> global mean pool -> dense 1280 -> hard-swish -> classifier.

The SE squeeze is the paper's: the expanded width x ``se_ratio``,
rounded to a multiple of ``se_divisor`` (``_make_divisible`` of the
paper's implementation).  Departure shared with the served entry:
convolutions carry no bias and no batch norm (see the configuration's
``assumed``).
"""

from __future__ import annotations

from typing import Dict, List

from .common import Block, act, block_leaves, chain, conv, dense, \
    make_divisible, pointwise, round_filters


def blocks(cfg: dict) -> List[Block]:
    wm = cfg["width_mult"]
    out: List[Block] = []
    c_in = round_filters(cfg["stem_c"], wm)
    for c_mid, c_out, k, s, se, a in cfg["blocks"]:
        c_mid = round_filters(c_mid, wm)
        c_out = round_filters(c_out, wm)
        out.append(Block(
            c_in=c_in, c_mid=c_mid, c_out=c_out, k=k, s=s,
            c_se=make_divisible(c_mid * cfg["se_ratio"], cfg["se_divisor"])
            if se else 0,
            act=a, se_act="relu", gate_act="hard_sigmoid"))
        c_in = c_out
    return out


def leaves(cfg: dict) -> Dict[str, object]:
    bl = blocks(cfg)
    wm = cfg["width_mult"]
    stem_c = round_filters(cfg["stem_c"], wm)
    head_c = round_filters(cfg["head_c"], wm)
    cls_c = round_filters(cfg["cls_c"], wm)
    tree: Dict[str, object] = {
        "stem": ((3, 3, 3, stem_c), "normal", 1.0),
        "head": ((bl[-1].c_out, head_c), "normal", 2.0),
        "fc": ((head_c, cls_c), "normal", 2.0),
        "cls_w": ((cls_c, cfg["num_classes"]), "normal", 1.0),
        "cls_b": ((cfg["num_classes"],), "zeros", 0.0),
    }
    for i, b in enumerate(bl):
        tree[f"block{i}"] = block_leaves(b)
    return tree


def head_layers(cfg: dict):
    """(c_in, c_out) of the dense layers after the pool."""
    wm = cfg["width_mult"]
    head_c = round_filters(cfg["head_c"], wm)
    cls_c = round_filters(cfg["cls_c"], wm)
    return [(head_c, cls_c), (cls_c, cfg["num_classes"])]


def forward(params: dict, images, cfg: dict, passes=None):
    """(B, H, W, 3) images -> (B, num_classes) logits, in the dtype of
    ``params`` and ``images`` (``passes``: see ``common.contract``)."""
    x = act(conv(images, params["stem"], 2, passes), "hard_swish")
    x = chain(x, params, blocks(cfg), passes)
    x = act(pointwise(x, params["head"], passes), "hard_swish")
    x = x.mean(axis=(1, 2))
    x = act(dense(x, params["fc"], passes), "hard_swish")
    return dense(x, params["cls_w"], passes) + params["cls_b"]
