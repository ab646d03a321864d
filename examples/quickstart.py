"""Quickstart: the paper's ConvDK dataflow in five minutes.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.schedule import make_schedule, is_exact_cover
from repro.core.convdk import dwconv2d_convdk, dwconv2d_oracle
from repro.core.tiling import DWLayer, plan_layer
from repro.core.perfmodel import cost_ws_base, cost_ws_convdk, reduction
from repro.kernels import convdk_depthwise2d, depthwise2d_ref

enable_compile_cache()

# 1. The number theory: the paper's worked example (k=3, s=2, N=30).
sched = make_schedule(k=3, s=2, N=30)
print(f"ConvDK schedule k=3 s=2 N=30: l={sched.l} shift cycles, "
      f"m1={sched.m1}, n1={sched.n1}")
print(f"  cycle a=0 computes outputs m = {sched.cycles[0].ms[:5]}...")
print(f"  Theorem 2 exact cover: {is_exact_cover(sched)}")

# 2. ConvDK computes the SAME depthwise conv, with one strip load per row.
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(8, 24, 24)), jnp.float32)       # (C, H, W)
k = jnp.asarray(rng.normal(size=(8, 3, 3)), jnp.float32)
out_dk = dwconv2d_convdk(x, k, stride=1, padding="SAME")
out_ref = dwconv2d_oracle(x, k, stride=1, padding="SAME")
print(f"\nConvDK == lax depthwise conv: "
      f"{bool(jnp.allclose(out_dk, out_ref, atol=1e-4))}")

# 3. The BIG/LITTLE scheduler picks the macro plan (Fig. 5's example).
plan = plan_layer(DWLayer(c=128, h=24, w=24, k=3, s=1))
print(f"\n128x24x24 DWConv -> {plan.mode} scheduler, N_ch={plan.n_ch}, "
      f"TM utilization {plan.tm_utilization:.0%}")

# 4. Buffer traffic: the paper's headline.
layer = DWLayer(c=512, h=14, w=14, k=3, s=1)
base, ours = cost_ws_base(layer), cost_ws_convdk(layer)
print(f"512x14x14: buffer traffic {base.buffer_words} -> {ours.buffer_words} "
      f"words ({reduction(base.buffer_words, ours.buffer_words):.1f}% less)")

# 5. The TPU kernel (Pallas; interpreted on a CPU) — same dataflow idea:
#    strip resident in VMEM, k shifted re-reads, channels on the lanes.
xb = jnp.asarray(rng.normal(size=(2, 14, 14, 32)), jnp.float32)   # NHWC
kb = jnp.asarray(rng.normal(size=(3, 3, 32)), jnp.float32)
got = convdk_depthwise2d(xb, kb, stride=1, padding="SAME")
want = depthwise2d_ref(xb, kb, stride=1, padding="SAME")
print(f"\nPallas ConvDK kernel == oracle: "
      f"{bool(jnp.allclose(got, want, atol=1e-4))}")
