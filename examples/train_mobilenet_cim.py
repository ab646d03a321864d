"""Train a MobileNetV1-style depthwise-separable CNN whose separable blocks
run the FUSED ConvDK Pallas kernel (DW taps + mid-block ReLU + 1x1 PW in one
VMEM residency; interpret mode on CPU) — the paper's own model family, end
to end trainable through the paper's dataflow with one HBM read per block.

    PYTHONPATH=src python examples/train_mobilenet_cim.py [--steps 60]
    PYTHONPATH=src python examples/train_mobilenet_cim.py --staged  # A/B

``--staged`` flips the routing flag in ``repro.configs.base`` back to the
two-kernel pipeline (stage_row_strips -> DW kernel -> HBM -> PW matmul) so
the two executables can be compared on the same run.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.base import kernel_config, set_kernel_config
from repro.models.common import separable_block, separable_def
from repro.models.param import P, materialize


def model_def(c0=16, n_blocks=3, n_classes=10):
    p = {"stem": P((3, 3, 3, c0), (None, None, None, None))}
    c = c0
    for i in range(n_blocks):
        p[f"sep{i}"] = separable_def(c, c * 2, k=3)
        c *= 2
    p["head"] = P((c, n_classes), (None, None))
    return p


def forward(params, x):
    # stem: ordinary 3x3 conv stride 2
    x = jax.lax.conv_general_dilated(
        x, params["stem"], (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x = jax.nn.relu(x)
    i = 0
    while f"sep{i}" in params:
        # DW + ReLU + PW + ReLU: ONE fused ConvDK kernel per block (the
        # staged two-kernel path when --staged flips the config flag)
        x = separable_block(params[f"sep{i}"], x, stride=2,
                            dw_act="relu", act="relu")
        i += 1
    x = x.mean(axis=(1, 2))                      # global average pool
    return x @ params["head"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--staged", action="store_true",
                    help="route separable blocks through the staged "
                         "two-kernel pipeline instead of the fused kernel")
    args = ap.parse_args()
    enable_compile_cache()
    set_kernel_config(fused_separable=not args.staged)

    params = materialize(model_def(), jax.random.key(0))

    def batch(step):
        r = np.random.default_rng((0, step))
        y = r.integers(0, 10, (32,))
        x = r.normal(size=(32, 32, 32, 3)).astype(np.float32) * 0.1
        # class-dependent blob so the task is learnable
        for b, cls in enumerate(y):
            x[b, cls:cls + 8, cls:cls + 8, :] += 1.0
        return jnp.asarray(x), jnp.asarray(y)

    @jax.jit
    def step(params, x, y):
        def loss_fn(p):
            logits = forward(p, x)
            logz = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
            return (logz - gold).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
        return params, loss

    losses = []
    for i in range(args.steps):
        x, y = batch(i)
        params, loss = step(params, x, y)
        losses.append(float(loss))
        if (i + 1) % 10 == 0:
            print(f"step {i+1}: loss {losses[-1]:.3f}")
    path = "fused" if kernel_config().fused_separable else "staged"
    print(f"\nloss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({'DESCENDED' if losses[-1] < losses[0] * 0.7 else 'check'}) — "
          f"separable blocks ran the {path} ConvDK Pallas pipeline")


if __name__ == "__main__":
    main()
