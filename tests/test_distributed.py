"""Distributed-correctness tests: run a subprocess with 8 virtual host
devices and check that sharded execution (FSDP x TP mesh, including the
shard_map expert-parallel MoE) is NUMERICALLY IDENTICAL to unsharded
execution, and that the sharding rule table produces sane specs."""

import os
import subprocess
import sys
import textwrap

import pytest

from jax.sharding import PartitionSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch
    from repro.models.model import forward, model_def
    from repro.models.param import materialize, logical_axes
    from repro.sharding import tree_shardings, spec_for
    from repro.compat import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert len(jax.devices()) == 8, jax.devices()
    arch = os.environ["TEST_ARCH"]
    cfg = get_arch(arch).smoke
    if cfg.family == "moe":
        # capacity is computed per token-shard: make it generous so NO tokens
        # drop in either execution and outputs must match exactly (default
        # 1.25 keeps drop semantics for perf runs)
        import dataclasses
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    pdefs = model_def(cfg)
    params = materialize(pdefs, jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)

    # unsharded reference (single device semantics)
    ref = forward(params, {"tokens": toks}, cfg)

    mesh = make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        p_sh = tree_shardings(logical_axes(pdefs), params, mesh)
        params_s = jax.device_put(params, p_sh)
        toks_s = jax.device_put(
            toks, NamedSharding(mesh, spec_for(["batch", None],
                                               toks.shape, mesh)))
        out = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg))(
            params_s, toks_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    print("DISTRIBUTED_OK", arch)
""")


@pytest.mark.parametrize("arch", ["gemma-2b", "granite-moe-3b-a800m",
                                  "mamba2-2.7b", "recurrentgemma-9b"])
def test_sharded_equals_unsharded(arch):
    env = dict(os.environ, TEST_ARCH=arch,
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert f"DISTRIBUTED_OK {arch}" in res.stdout


def test_spec_for_drops_nondivisible():
    from repro.compat import make_mesh
    from repro.sharding import spec_for
    mesh = make_mesh((1, 1), ("data", "model"))
    # size-1 mesh axes -> everything replicated
    spec = spec_for(("embed", "heads"), (64, 8), mesh)
    assert spec == PartitionSpec(None, None)


def test_spec_for_rules():
    from repro.sharding import spec_for

    class FakeMesh:
        shape = {"data": 4, "model": 2}
    mesh = FakeMesh()
    assert spec_for(("embed", "ff"), (64, 64), mesh) == \
        PartitionSpec("data", "model")
    # kv_heads = 1 (MQA) is not divisible by model=2 -> dropped
    assert spec_for(("embed", "kv_heads"), (64, 1), mesh) == \
        PartitionSpec("data", None)
    # batch maps to the (pod, data) group; pod absent -> data only
    assert spec_for(("batch", None), (8, 16), mesh) == \
        PartitionSpec("data", None)
