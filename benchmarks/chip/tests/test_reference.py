"""The plain references agree with the program's interpret-mode apply at
a small width, on weights the benchmark makes."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

import run
import weights
import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("name", ["efficientnet_b0", "mobilenet_v3_large"])
def test_reference_matches_program_at_small_width(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg.update(width_mult=0.25, num_classes=10)
    params = weights.make_params(work.reference_module(cfg).leaves(cfg), 7)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    got = np.asarray(run.program_apply(cfg)(params, x))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(run.reference_forward(cfg)(params, x))
    assert got.shape == want.shape == (2, 10)
    assert np.all(np.isfinite(want)) and np.abs(want).max() > 0
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


def test_weights_follow_the_seed():
    cfg = json.loads((CONFIGS / "efficientnet_b0.json").read_text())
    cfg.update(width_mult=0.25, num_classes=10)
    leaves = work.reference_module(cfg).leaves(cfg)
    big = 2 ** 31 + 977
    a = weights.make_params(leaves, big)
    b = weights.make_params(leaves, big)
    c = weights.make_params(leaves, big + 1)
    assert np.array_equal(a["stem"], b["stem"])
    assert not np.array_equal(a["stem"], c["stem"])


def test_three_pass_split_rounds_the_head_and_keeps_the_tail():
    import jax.numpy as jnp
    from reference.common import _split
    a = jax.random.normal(jax.random.key(3), (4096,)) * 1e3
    head, tail = _split(a)
    assert head.dtype == tail.dtype == jnp.bfloat16
    assert bool(jnp.all(head == a.astype(jnp.bfloat16)))
    rebuilt = head.astype(jnp.float32) + tail.astype(jnp.float32)
    assert float(jnp.abs(rebuilt - a).max() / jnp.abs(a).max()) < 2 ** -15


def test_three_pass_control_lies_between_float32_and_bfloat16():
    cfg = json.loads((CONFIGS / "mobilenet_v3_large.json").read_text())
    cfg.update(width_mult=0.25, num_classes=10)
    ref = work.reference_module(cfg)
    params = weights.make_params(ref.leaves(cfg), 11)
    x = jax.random.normal(jax.random.key(4), (2, 32, 32, 3))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(params, x, cfg))
        three = np.asarray(jax.jit(
            lambda p, i: ref.forward(p, i, cfg, passes=3))(params, x))
    err = np.abs(three - want).max() / np.abs(want).max()
    assert 1e-6 < err < 1e-3, err
