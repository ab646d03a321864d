"""Weights and keys from ``--seed``.

The weights of a configuration are made on the device in one jitted call,
in the dtype the configuration serves, from the leaf table of its plain
reference (``reference/<family>.py`` ``leaves``).  The program and the
reference are handed the same arrays; neither makes its own.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from reference.common import init_leaf


def key_for(seed: int, stream: int):
    """A JAX key for one named stream of a run, from any whole ``seed``
    (64 bits are kept: the low 32 seed the key, the high 32 fold in)."""
    s = seed % (1 << 64)
    key = jax.random.key(s & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, s >> 32), stream)


def _flatten(tree, prefix=()) -> List[Tuple[Tuple[str, ...], tuple]]:
    out = []
    for name in sorted(tree):
        v = tree[name]
        if isinstance(v, dict):
            out.extend(_flatten(v, prefix + (name,)))
        else:
            out.append((prefix + (name,), v))
    return out


def make_params(leaves: Dict[str, object], seed: int, dtype=jnp.float32):
    """Every leaf of ``leaves`` from an independent fold of the seed."""
    flat = _flatten(leaves)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        tree: Dict[str, object] = {}
        for (path, leaf), k in zip(flat, keys):
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = init_leaf(leaf, k, dtype)
        return tree

    return build(key_for(seed, 0))
