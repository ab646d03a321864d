"""The §Perf optimization knobs must not change numerics.

Each knob is validated two ways: (a) single-device — flag on == flag off
bit-near; (b) 8-virtual-device subprocess — sharded+flagged == unsharded
reference (the same harness as test_distributed).
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model import forward, model_def
from repro.models.param import materialize

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_vocab_padding_preserves_logits():
    cfg = get_arch("granite-moe-3b-a800m").smoke
    cfgp = dataclasses.replace(cfg, vocab_pad_multiple=16)
    assert cfgp.padded_vocab % 16 == 0 and cfgp.padded_vocab >= cfg.vocab

    params = materialize(model_def(cfgp), jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    lg = forward(params, {"tokens": toks}, cfgp)
    assert lg.shape[-1] == cfgp.padded_vocab
    # padded classes are masked to -inf -> argmax never selects them
    assert int(jnp.argmax(lg, -1).max()) < cfg.vocab
    assert bool((lg[..., cfg.vocab:] < -1e29).all())


@pytest.mark.parametrize("flags", [
    {"seq_shard_attn": True},
    {"seq_shard_attn": True, "vocab_pad_multiple": 16},
    {"seq_shard_resid": True},
])
def test_knobs_noop_on_single_device(flags):
    """Without a mesh the knobs must be exact no-ops numerically."""
    cfg = get_arch("qwen1.5-4b").smoke
    params = materialize(model_def(cfg), jax.random.key(0))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    ref = forward(params, {"tokens": toks}, cfg)

    cfg2 = dataclasses.replace(cfg, **flags)
    if cfg2.padded_vocab == cfg.vocab:
        out = forward(params, {"tokens": toks}, cfg2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch
    from repro.models.model import forward, model_def
    from repro.models.param import materialize, logical_axes
    from repro.sharding import tree_shardings, spec_for
    from repro.compat import make_mesh
    from jax.sharding import NamedSharding

    cfg = get_arch("qwen1.5-4b").smoke
    # 4-way model axis; qwen smoke has 4 heads -> divisible, so FORCE the
    # seq-shard path by giving it 3 kv heads? instead use n_kv_heads=2 with
    # model=4 -> non-divisible -> SP engages.
    cfg = dataclasses.replace(cfg, n_kv_heads=2, seq_shard_attn=True,
                              seq_shard_resid=True, vocab_pad_multiple=16)
    pdefs = model_def(cfg)
    params = materialize(pdefs, jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    ref = forward(params, {"tokens": toks}, cfg)   # no mesh: knobs dormant

    mesh = make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        p_sh = tree_shardings(logical_axes(pdefs), params, mesh)
        params_s = jax.device_put(params, p_sh)
        toks_s = jax.device_put(toks, NamedSharding(
            mesh, spec_for(["batch", None], toks.shape, mesh)))
        out = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg))(
            params_s, toks_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-3, atol=3e-3)
    print("PERF_KNOBS_OK")
""")


def test_knobs_sharded_equal_unsharded():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "PERF_KNOBS_OK" in res.stdout
