"""A cell of the benchmark cut to a size the CPU runs in seconds, and the
faults its runs must catch."""

import argparse
import json

import jax
import jax.numpy as jnp

import run

# on the CPU the program and the reference agree to ~1e-6 of the logit
# scale (both float32 at "highest"); the three-pass control reads ~1e-4
TINY_LIMITS = {"logit_err": {"limit": 2e-5}, "nonfinite": {"limit": 0}}


def tiny_cell(name: str) -> run.Cell:
    """A cell of ``BENCHMARK.json``, cut down."""
    cell = run.load_cell(name)
    cell.cfg.update(width_mult=0.25, num_classes=10)
    cell.mix.update(resolution=32, batch=4, distinct_batches=2, compare=6)
    cell.limits = TINY_LIMITS
    return cell


def tiny_run(cell: run.Cell, seconds: float = 0.5, seed: int = 2 ** 31 + 7,
             wrap=None) -> dict:
    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=seconds,
                              trace=0)
    peaks = json.loads((run.HERE / "peaks.json").read_text())["TPU v5 lite"]
    return run.run(args, cell=cell, devices=jax.devices()[:1], peaks=peaks,
                   wrap=wrap)


def answer_altered(apply):
    """Every image's first logit moved by its largest logit's size."""
    def f(params, images):
        out = apply(params, images)
        return out.at[:, 0].add(jnp.abs(out).max(axis=1))
    return f


def half_batch_mean(apply):
    """The first half of the batch left out; its rows get the mean of
    the rest."""
    def f(params, images):
        h = images.shape[0] // 2
        out = apply(params, images[h:])
        fill = jnp.broadcast_to(out.mean(axis=0), (h,) + out.shape[1:])
        return jnp.concatenate([fill, out])
    return f
