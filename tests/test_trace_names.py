"""Names on the device: every op of a served model's compiled program sits
under a ``stem``, ``head`` or block scope, every Pallas kernel carries the
``name=`` of its pass, and the schedule solvers time themselves as the
span ``autotune.plan``.  The device trace and the benchmark's
``scopes.py`` read these names."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import telemetry
from repro.models import mbconv as M
from repro.models.param import materialize

SCOPE = re.compile(r"(stem|head|mbconv\d+|fusedmb\d+)")

MODELS = {
    "efficientnet_b0": (M.EffNetConfig, M.efficientnet_b0_def,
                        M.efficientnet_b0_apply, ["mbconv"] * 16),
    "mobilenet_v3_large": (M.MobileNetV3Config, M.mobilenet_v3_def,
                           M.mobilenet_v3_apply, ["mbconv"] * 15),
    "efficientnet_v2_s": (M.EffNetV2Config, M.efficientnet_v2_s_def,
                          M.efficientnet_v2_s_apply,
                          ["fusedmb"] * 10 + ["mbconv"] * 30),
}


@pytest.mark.parametrize("model", sorted(MODELS))
def test_every_program_op_sits_under_a_scope(model):
    config, define, apply, families = MODELS[model]
    cfg = config(width_mult=0.25, num_classes=10)
    params = materialize(define(cfg), jax.random.key(0))
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    hlo = jax.jit(lambda p, x: apply(p, x, cfg)).lower(
        params, x).compile().as_text()
    paths = re.findall(r'op_name="(jit\([^"]*)"', hlo)
    assert paths
    seen = set()
    for path in paths:
        scope = next((p for p in path.split("/") if SCOPE.fullmatch(p)),
                     None)
        assert scope is not None, path
        seen.add(scope)
    assert seen == {"stem", "head"} | {f"{family}{i}"
                                       for i, family in enumerate(families)}


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _mbconv(mode):
    from repro.kernels import convdk_mbconv_fused
    args = (_f32(2, 16, 16, 8), _f32(8, 32), _f32(3, 3, 32), _f32(32, 8),
            _f32(8), _f32(8, 32), _f32(32), _f32(32, 8))
    return (lambda *a: convdk_mbconv_fused(*a, mode=mode, interpret=False),
            args)


def _dw():
    from repro.kernels import convdk_depthwise2d
    return (lambda x, w: convdk_depthwise2d(x, w, interpret=False),
            (_f32(2, 16, 16, 128), _f32(3, 3, 128)))


def _separable():
    from repro.kernels import convdk_fused_separable
    return (lambda x, wd, wp: convdk_fused_separable(x, wd, wp,
                                                     interpret=False),
            (_f32(2, 16, 16, 128), _f32(3, 3, 128), _f32(128, 128)))


def _fusedmb():
    from repro.kernels import convdk_fusedmb_fused
    return (lambda x, wc, wp: convdk_fusedmb_fused(x, wc, wp,
                                                   interpret=False),
            (_f32(2, 16, 16, 8), _f32(3, 3, 8, 32), _f32(32, 8)))


def _conv1d():
    from repro.kernels import convdk_causal_conv1d
    return (lambda x, w: convdk_causal_conv1d(x, w, interpret=False),
            (_f32(2, 64, 128), _f32(4, 128)))


KERNELS = {
    "mbconv_pass1": lambda: _mbconv("retain"),
    "mbconv_pass2_retain": lambda: _mbconv("retain"),
    "mbconv_pass2_recompute": lambda: _mbconv("recompute"),
    "dw2d": _dw,
    "fused_separable": _separable,
    "fusedmb": _fusedmb,
    "conv1d": _conv1d,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_call_carries_its_name(name):
    """Lowered for the TPU (no chip needed): the kernel's op sits under a
    scope of its ``name=``, so its HLO ``op_name`` and instruction name
    say which pass it is."""
    fn, args = KERNELS[name]()
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    assert "tpu_custom_call" in text
    locs = set(re.findall(r'loc\("([^"]*pallas_call)"', text))
    assert f"{name}/pallas_call" in {"/".join(loc.split("/")[-2:])
                                      for loc in locs}


def test_plan_span_times_hits_and_nested_solves_once():
    from repro.core.autotune import get_mbconv_schedule, get_network_plan
    telemetry.reset()
    args = (2, 16, 16, 8, 32, 8, 3, 1)
    get_mbconv_schedule(*args)
    get_mbconv_schedule(*args)                 # a cache hit is timed too
    st = telemetry.get_telemetry().span_stat("autotune.plan")
    assert st.count == 2 and st.total_s > 0
    rows = [(16, 16, 8, 32, 8, 3, 1), (16, 16, 8, 32, 16, 3, 2)]
    with telemetry.span("autotune.plan"):      # an outer solve: nested
        get_network_plan(rows, 2)              # entries count once
    assert telemetry.get_telemetry().span_stat("autotune.plan").count == 3
    assert "autotune.solve.mbconv" not in telemetry.snapshot()["counters"]


def test_blockgraph_lower_names_each_node():
    """``BlockGraph.lower`` runs each node under its own name; no counter
    is left behind."""
    cfg = M.EffNetConfig(width_mult=0.25, num_classes=10)
    params = materialize(M.efficientnet_b0_def(cfg), jax.random.key(0))
    telemetry.reset()
    jaxpr = jax.make_jaxpr(lambda p, x: M.efficientnet_b0_apply(p, x, cfg))(
        params, jnp.zeros((1, 32, 32, 3), jnp.float32))
    stacks = {str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns}
    assert {"stem", "head", "mbconv0", "mbconv15"} <= {
        s.split("/")[0] for s in stacks}
    assert not any(k.startswith("blockgraph.")
                   for k in telemetry.snapshot()["counters"])
