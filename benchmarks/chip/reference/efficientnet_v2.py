"""Plain reference of EfficientNet-V2-S (Tan & Le 2021, arXiv:2104.00298,
Table 2, eval resolution 384): stem 3x3/2 conv to 24 -> silu ->
Fused-MBConv stages 1-3 -> MBConv stages 4-6 (SE with silu squeeze and
sigmoid gate, squeeze width = block input channels x ``se_ratio``) -> 1x1
head conv to 1280 -> silu -> global mean pool -> classifier.

A Fused-MBConv block is one dense k x k / s convolution -> silu, then a
1x1 linear projection when the expansion is not 1, plus the identity
residual where shapes allow.  At expansion 1 (stage 1) there is no
projection: the k x k convolution goes straight to the output channels
and the activation follows it, as in the paper's code.

Departures from the paper, shared with the served entry: no batch norm
and no convolution bias (a norm at its initial state folds to a zero
bias; see the configuration's ``assumed``), no dropout and no stochastic
depth (both are training-time only).  Everything else is the published
network at the widths of the configuration file.  The random weights'
scales are set below (``CONV_SCALE``, ``PROJ_SCALE``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Union

import numpy as np

from .common import Block, act, block, block_leaves, conv, dense, \
    pointwise, round_filters


@dataclasses.dataclass(frozen=True)
class FusedBlock:
    """One Fused-MBConv block at logical widths.  ``c_mid`` is the dense
    conv's output width (``c_out`` at expansion 1); ``c_se`` is 0 (no
    squeeze-and-excitation), as ``work.block_work`` reads it."""

    c_in: int
    c_mid: int
    c_out: int
    k: int
    s: int
    projects: bool
    c_se: int = 0
    act: str = "silu"

    @property
    def expands(self) -> bool:
        return self.c_mid != self.c_in

    @property
    def residual(self) -> bool:
        return self.s == 1 and self.c_in == self.c_out


def blocks(cfg: dict) -> List[Union[FusedBlock, Block]]:
    wm = cfg["width_mult"]
    out: List[Union[FusedBlock, Block]] = []
    c_in = round_filters(cfg["stem_c"], wm)
    for family, expand, k, s, c_out, repeats in cfg["stages"]:
        c_out = round_filters(c_out, wm)
        for i in range(repeats):
            stride = s if i == 0 else 1
            if family == "fusedmb":
                out.append(FusedBlock(
                    c_in=c_in, c_mid=c_out if expand == 1 else c_in * expand,
                    c_out=c_out, k=k, s=stride, projects=expand != 1))
            else:
                out.append(Block(
                    c_in=c_in, c_mid=c_in * expand, c_out=c_out, k=k,
                    s=stride, c_se=max(1, int(c_in * cfg["se_ratio"])),
                    act="silu", se_act="silu", gate_act="sigmoid"))
            c_in = c_out
    return out


# Init scales (std = scale / sqrt(fan_in)): He's sqrt(2) for the dense
# conv that silu follows, 1 for every linear projection into a residual
# add.  At B0's scale of 2 on the projections, V2-S's 34 residual blocks
# grow the activations ten-million-fold, and float32 rounding alone then
# moves the logits by 2e-5 of their scale (0.3 at width 0.25, 32 x 32).
CONV_SCALE = float(np.sqrt(2.0))
PROJ_SCALE = 1.0


def _fused_leaves(b: FusedBlock) -> Dict[str, object]:
    p: Dict[str, object] = {
        "conv": ((b.k, b.k, b.c_in, b.c_mid), "normal", CONV_SCALE)}
    if b.projects:
        p["proj"] = ((b.c_mid, b.c_out), "normal", PROJ_SCALE)
    return p


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
        tree[f"block{i}"] = _fused_leaves(b) if isinstance(b, FusedBlock) \
            else dict(block_leaves(b),
                      proj=((b.c_mid, b.c_out), "normal", PROJ_SCALE))
    return tree


def head_layers(cfg: dict):
    """(c_in, c_out) of the dense layers after the pool."""
    head_c = round_filters(cfg["head_c"], cfg["width_mult"])
    return [(head_c, cfg["num_classes"])]


def fused_block(x, p: dict, b: FusedBlock, passes=None):
    """dense k x k / s conv -> silu -> [project 1x1 (linear)], plus the
    identity residual where shapes allow."""
    y = act(conv(x, p["conv"], b.s, passes), b.act)
    if b.projects:
        y = pointwise(y, p["proj"], passes)
    return y + x if b.residual else y


def forward(params: dict, images, cfg: dict, passes=None):
    """(B, H, W, 3) images -> (B, num_classes) logits, in the dtype of
    ``params`` and ``images`` (``passes``: see ``common.contract``)."""
    x = act(conv(images, params["stem"], 2, passes), "silu")
    for i, b in enumerate(blocks(cfg)):
        run = fused_block if isinstance(b, FusedBlock) else block
        x = run(x, params[f"block{i}"], b, passes)
    x = act(pointwise(x, params["head"], passes), "silu")
    x = x.mean(axis=(1, 2))
    return dense(x, params["cls_w"], passes) + params["cls_b"]
