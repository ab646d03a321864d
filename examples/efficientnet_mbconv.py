"""EfficientNet-B0 end to end through the two-pass fused MBConv pipeline.

Prints the per-layer two-pass schedule table (tile_h + retain/recompute
choice and the modeled HBM traffic vs the staged DW->HBM->SE->PW baseline)
for the full-size B0, then runs a width-scaled B0 forward + one training
step with every MBConv block executing the fused ConvDK kernels (interpret
mode on CPU).

    PYTHONPATH=src python -m examples.efficientnet_mbconv [--hw 32]
    PYTHONPATH=src python -m examples.efficientnet_mbconv --staged   # A/B
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.base import set_kernel_config
from repro.configs.efficientnet_b0 import efficientnet_b0_smoke
from repro.core.autotune import get_mbconv_schedule
from repro.core.workloads import EFFICIENTNET_B0_MBCONV
from repro.models.mbconv import (
    effnet_block_specs,
    efficientnet_b0_apply,
    efficientnet_b0_def,
)
from repro.models.param import count_params, materialize


def schedule_table():
    print("== EfficientNet-B0 two-pass fused MBConv schedules (batch 1) ==")
    print(f"{'layer':<12}{'c_in':>5}{'c_mid':>6}{'c_out':>6}{'hw':>4}"
          f"{'k':>3}{'s':>3}{'tile_h':>7}{'mode':>11}{'saving':>8}")
    total_f = total_s = 0
    for i, (ci, co, e, k, s, hw) in enumerate(EFFICIENTNET_B0_MBCONV):
        sch = get_mbconv_schedule(1, hw, hw, ci, ci * e, co, k, s)
        total_f += sch.traffic.total_bytes
        total_s += sch.staged_traffic.total_bytes
        print(f"{'b0_mbconv' + str(i):<12}{ci:>5}{ci * e:>6}{co:>6}{hw:>4}"
              f"{k:>3}{s:>3}{sch.tile_h:>7}{sch.mode:>11}"
              f"{100 * sch.modeled_saving:>7.1f}%")
    print(f"network total: fused {total_f / 1e6:.1f} MB vs staged "
          f"{total_s / 1e6:.1f} MB "
          f"({100 * (1 - total_f / total_s):.1f}% HBM traffic avoided)\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hw", type=int, default=32,
                    help="input resolution for the smoke forward/backward")
    ap.add_argument("--staged", action="store_true",
                    help="route MBConv blocks through the staged "
                         "DW->HBM->SE->PW baseline instead of the two-pass "
                         "fused pipeline")
    args = ap.parse_args()
    enable_compile_cache()
    set_kernel_config(fused_mbconv=not args.staged)

    schedule_table()

    cfg = efficientnet_b0_smoke()
    params = materialize(efficientnet_b0_def(cfg), jax.random.key(0))
    specs = effnet_block_specs(cfg)
    print(f"smoke B0: width x{cfg.width_mult}, {len(specs)} MBConv blocks, "
          f"{count_params(efficientnet_b0_def(cfg)):,} params, "
          f"input {args.hw}x{args.hw}")

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, args.hw, args.hw, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.num_classes, (2,)))

    logits = efficientnet_b0_apply(params, x, cfg)
    print(f"forward: logits {logits.shape}, "
          f"finite={bool(jnp.isfinite(logits).all())}")

    def loss_fn(p):
        lg = efficientnet_b0_apply(p, x, cfg)
        logz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, y[:, None], -1)[:, 0]
        return (logz - gold).mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    gnorm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)))
    path = "staged" if args.staged else "two-pass fused"
    print(f"backward: loss {float(loss):.3f}, grad norm {float(gnorm):.3f} — "
          f"every MBConv block ran the {path} ConvDK pipeline")


if __name__ == "__main__":
    main()
