"""Thin wrappers over the JAX surface the repo builds on (JAX 0.9.0).

Everything that builds a mesh, enters ``shard_map`` or issues a Pallas
DMA goes through this module, so those call shapes live in one
place: meshes with explicit Auto axis types, ``shard_map`` without
replication checking, and the strip-staging engine's async copies.
"""

from __future__ import annotations

import os
import sys
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — the fused conv
    wrappers psum explicitly."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Pallas DMA surface (the strip-staging engine in kernels/staging.py)
#
# The production rendering of the fused ConvDK kernels keeps the input in
# the ANY/HBM memory space and DMAs each halo'd strip window into VMEM
# scratch with ``pltpu.make_async_copy``.  Interpret mode (the CPU test
# backend) executes the SAME DMA-structured code path — the interpreter
# implements the copy/semaphore primitives — so parity tests genuinely
# exercise the staging structure.
# ---------------------------------------------------------------------------

def _pltpu():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu


def pallas_async_copy(src, dst, sem, priority=None):
    """``pltpu.make_async_copy``, with an optional DMA stream priority.

    ``priority`` requests a DMA stream priority for the copy (prefetches
    want the low-priority background stream, ``priority=1``, so demand
    fetches overtake them).  It is passed through only where
    ``make_async_copy`` takes it and dropped otherwise —
    ``pallas_dma_priority_supported()`` reports which happened, and the
    bench records the knob as unsupported rather than pretending it was
    exercised."""
    pltpu = _pltpu()
    if priority is not None and pallas_dma_priority_supported():
        return pltpu.make_async_copy(src, dst, sem, priority=priority)
    return pltpu.make_async_copy(src, dst, sem)


def pallas_dma_priority_supported() -> bool:
    """Whether ``make_async_copy`` accepts a ``priority`` argument here."""
    import inspect
    return "priority" in inspect.signature(
        _pltpu().make_async_copy).parameters


# ---------------------------------------------------------------------------
# custom_vjp residual-forwarding bug: probe + barrier
#
# When a ``custom_vjp`` op whose residuals ARE its inputs sits under
# ``jax.jit`` with a ``shard_map`` in its primal (the cached sharded conv
# entry points), the installed JAX's partial-eval forwards the inputs
# straight to the residual outputs; on affected builds the sharded
# MBConv's ``w_dw`` cotangent then comes back multiplied by the model-axis
# size (the forwarded residuals' shardings re-partition the reference-vjp
# backward).  An ``optimization_barrier`` around the residual tuple keeps
# the residuals distinct values, restoring exact gradients.
#
# The barrier is PROBE-GATED: ``residual_forwarding_probe`` runs the real
# sharded MBConv gradient once, at a tiny shape on a (2, 2) slice of the
# local devices, and compares the ``w_dw`` cotangent against the reference
# VJP it is defined to equal.  On fixed JAX builds the barrier therefore
# auto-disables; where the probe cannot run (fewer than 4 devices, or a
# probe failure — reported on stderr) the barrier stays on — it is
# harmless when the bug is absent.  The probe runs the kernels as the
# process would (compiled on a TPU, interpreted on the CPU), so a verdict
# on a four-chip host is about the compiled path.
# ``CONVDK_RESIDUAL_BARRIER`` / ``set_residual_barrier`` force the
# decision ("on" | "off" | "auto").
# ---------------------------------------------------------------------------

_BARRIER_ENV = "CONVDK_RESIDUAL_BARRIER"
_BARRIER_MODES = ("auto", "on", "off")
_barrier_mode = os.environ.get(_BARRIER_ENV, "auto").lower()
if _barrier_mode not in _BARRIER_MODES:   # a typo'd override must be LOUD —
    raise ValueError(                     # silently probing anyway inverts
        f"{_BARRIER_ENV} must be one of {_BARRIER_MODES}, "
        f"got {_barrier_mode!r}")         # the operator's intent
_probe_result: Optional[str] = None    # "buggy" | "fixed" | "unprobed"
_probing = False


def set_residual_barrier(mode: str) -> str:
    """Force the residual barrier "on" / "off", or restore "auto" (the
    probe decides).  Returns the previous mode.  NOTE: the decision is
    baked into traces — clear the sharded entry-point caches
    (``convdk_sharded._sep_sharded_entry`` / ``_mbconv_sharded_entry``)
    when flipping it mid-process."""
    global _barrier_mode, _probe_result
    if mode not in _BARRIER_MODES:
        raise ValueError(f"mode must be one of {_BARRIER_MODES}, got {mode!r}")
    prev, _barrier_mode = _barrier_mode, mode
    if mode == "auto" and _probe_result == "unprobed":
        _probe_result = None   # retry an inconclusive probe; a concluded
    return prev                # buggy/fixed verdict is process-invariant


def residual_forwarding_probe() -> Optional[bool]:
    """Does THIS JAX build miscount custom_vjp residual-forwarded
    cotangents?  True = bug observed, False = exact without the barrier,
    None = cannot probe here (fewer than 4 devices, or the probe failed —
    the barrier then stays on).  The verdict is cached per process;
    inside an ambient trace (the probe's own computation would join it
    and leak tracers) nothing runs and nothing is cached — the next
    EAGER consult (the public wrappers make one per dispatch) resolves
    it."""
    global _probe_result
    if _probe_result is None:
        if not jax.core.trace_ctx.is_top_level():
            return None                # un-cached: retry when eager
        _probe_result = _run_forwarding_probe()
    return {"buggy": True, "fixed": False}.get(_probe_result)


def _run_forwarding_probe() -> str:
    global _probing
    if len(jax.devices()) < 4:
        return "unprobed"
    try:
        import numpy as np

        # lazy import: convdk_sharded imports this module at load time
        from .kernels.common import default_interpret
        from .kernels.convdk_sharded import (
            _mbconv_sharded_op,
            _sep_sharded_op,
        )
        from .kernels.ref import mbconv_ref, separable_ref

        interpret = default_interpret()

        mesh = make_mesh((2, 2), ("data", "model"))
        b, hw, ci, co, k, cse = 2, 4, 8, 4, 3, 1
        cm = ci                        # identity expand (ratio-1 block)

        def arr(seed, *shape):
            rng = np.random.default_rng(seed)
            return jax.numpy.asarray(rng.normal(size=shape) * 0.3,
                                     jax.numpy.float32)

        x = arr(0, b, hw, hw, ci)
        weights = (jax.numpy.eye(cm, dtype=jax.numpy.float32),
                   arr(1, k, k, cm), arr(2, cm, cse), arr(3, cse),
                   arr(4, cse, cm), arr(5, cm), arr(6, cm, co))

        # a fresh jit around the raw op: the probe must not populate (or
        # read) the production lru entry-point cache with a barrier-free
        # trace.  Structure matters, and mirrors the production entry
        # points exactly: ALL arrays are jit ARGUMENTS (input->output
        # forwarding only fires on jit inputs, not closure constants),
        # the jit returns the OP OUTPUT (the loss stays outside, as in
        # serving/training loops), and the loss DEPENDS on the primal
        # output ((out**2) — a constant cotangent does not tickle the
        # forwarding rewrite).
        entry = jax.jit(lambda *arrays: _mbconv_sharded_op(
            *arrays, mesh, 1, "SAME", 1, "retain", None, "silu", interpret,
            "strip_dma_db", "ring_allreduce", "replicated"))

        def loss(wd):
            out = entry(x, weights[0], wd, *weights[2:])
            return (out ** 2).sum()

        _probing = True               # trace the fwd WITHOUT the barrier
        try:
            got = jax.grad(loss)(weights[1])
        finally:
            _probing = False
        want = jax.grad(
            lambda wd: (mbconv_ref(x, weights[0], wd, *weights[2:],
                                   stride=1, exp_act=None) ** 2).sum(),
        )(weights[1])
        if not np.allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-3, atol=1e-3):
            return "buggy"

        # second leg: the SEPARABLE custom_vjp (3-tuple residuals, no
        # psum, c_out-sharded out_specs) — a build could rewrite one
        # family's forwarding and not the other's, and a "fixed" verdict
        # disables the barrier for BOTH
        w_pw = arr(7, ci, co)
        sep_entry = jax.jit(lambda *arrays: _sep_sharded_op(
            *arrays, mesh, 1, "SAME", 1, None, None, interpret,
            "strip_dma_db", "ring_allreduce", "replicated"))

        def sep_loss(wd):
            return (sep_entry(x, wd, w_pw) ** 2).sum()

        _probing = True
        try:
            got_s = jax.grad(sep_loss)(weights[1])
        finally:
            _probing = False
        want_s = jax.grad(
            lambda wd: (separable_ref(x, wd, w_pw, stride=1, dw_act=None,
                                      act=None) ** 2).sum())(weights[1])
        exact = np.allclose(np.asarray(got_s), np.asarray(want_s),
                            rtol=1e-3, atol=1e-3)
        return "fixed" if exact else "buggy"
    except Exception as e:            # any probe failure: keep the barrier,
        print(f"repro.compat: residual-forwarding probe failed, keeping "
              f"the barrier on: {type(e).__name__}: {e}",
              file=sys.stderr)        # and say why
        return "unprobed"


def residual_barrier_needed() -> bool:
    """The probe-gated decision ``residual_barrier`` applies (see the
    section doc): forced modes win (the env var seeds the initial mode,
    ``set_residual_barrier`` overrides it), otherwise the probe — with
    the barrier kept on wherever the probe is inconclusive."""
    if _barrier_mode == "on":
        return True
    if _barrier_mode == "off":
        return False
    return residual_forwarding_probe() is not False


def residual_barrier(res):
    """Block jit's input->output forwarding on a custom_vjp residual tuple
    (section doc above) — unless the probe shows this build is fixed, in
    which case the tuple passes through untouched."""
    if _probing or not residual_barrier_needed():
        return res
    return jax.lax.optimization_barrier(res)
