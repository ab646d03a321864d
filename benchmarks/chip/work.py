"""Work counts of a configuration, from its spec table alone.

FLOPs count two per multiply-add of every convolution and dense layer at
logical widths (activations, pooling, the SE gate multiply and residual
adds are left out, so a share of a peak built on them can only read
low).  Compulsory HBM bytes of a layer are its input, its weights and its
output, each moved once, at the configuration's dtype: the same number
whatever schedule or kernel runs the layer.  Nothing here reads the
program's traffic model.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class LayerWork:
    name: str
    flops_per_image: float
    act_bytes_per_image: float     # input + output, once each
    weight_bytes: float            # once per step, whatever the batch

    def bytes(self, batch: int) -> float:
        return batch * self.act_bytes_per_image + self.weight_bytes

    def roofline_s(self, batch: int, peak_flops: float,
                   hbm_bytes_per_s: float) -> float:
        """The least time the chip could take for this layer."""
        return max(batch * self.flops_per_image / peak_flops,
                   self.bytes(batch) / hbm_bytes_per_s)


def reference_module(cfg: dict):
    return importlib.import_module(f"reference.{cfg['reference']}")


def _same(n: int, s: int) -> int:
    return -(-n // s)


def block_work(cfg: dict, resolution: int) -> List[LayerWork]:
    """One ``LayerWork`` per block of the chain (expand, depthwise, SE
    and projection together: the block is the layer whose intermediate
    the fused dataflow keeps on chip)."""
    dt = np.dtype(cfg["dtype"]).itemsize
    h = _same(resolution, 2)
    out = []
    for i, b in enumerate(reference_module(cfg).blocks(cfg)):
        ho = _same(h, b.s)
        macs = ho * ho * b.k * b.k * b.c_mid + ho * ho * b.c_mid * b.c_out
        weights = b.k * b.k * b.c_mid + b.c_mid * b.c_out
        if b.expands:
            macs += h * h * b.c_in * b.c_mid
            weights += b.c_in * b.c_mid
        if b.c_se:
            macs += 2 * b.c_mid * b.c_se
            weights += 2 * b.c_mid * b.c_se + b.c_se + b.c_mid
        out.append(LayerWork(
            f"block{i}", 2.0 * macs,
            dt * (h * h * b.c_in + ho * ho * b.c_out), dt * weights))
        h = ho
    return out


def model_flops_per_image(cfg: dict, resolution: int) -> float:
    """FLOPs of one image through stem, blocks, head and dense layers."""
    ref = reference_module(cfg)
    blocks = ref.blocks(cfg)
    h = _same(resolution, 2)
    stem = 2.0 * h * h * 27 * blocks[0].c_in
    for b in blocks:
        h = _same(h, b.s)
    head_in, _ = ref.head_layers(cfg)[0]
    head = 2.0 * h * h * blocks[-1].c_out * head_in
    dense = sum(2.0 * ci * co for ci, co in ref.head_layers(cfg))
    return stem + sum(w.flops_per_image for w in block_work(cfg, resolution)) \
        + head + dense
