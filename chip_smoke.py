#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: full-width EfficientNet-B0 served
through ``serve.vision.VisionEngine`` with compiled Pallas kernels.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the sharded path on a (2, 2) mesh

One chip: builds the published B0 (width 1.0, 1000 classes, float32) from
``--seed``, serves 16 requests of mixed sides <= 224 in the 224 bucket at
batch 8, and checks that the bucket compiled once, that the compiled
program holds Mosaic kernels (``tpu_custom_call``: compiled, not
interpreted), and that every request's logits agree with a plain float32
reference of the same network.

``--chips 4``: serves the same 16 requests through
``VisionEngine(mesh=...)`` on a (2, 2) ("data", "model") mesh of the four
local chips, checks that the sharded MBConv wrappers ran, and compares the
logits with the one-device float32 reference.  It runs nothing else.

Exits non-zero when JAX finds no TPU (there is no CPU fallback) and when
any check fails.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Every earlier
number is a smoke figure, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

BUCKET = 224
BATCH = 8
N_REQUESTS = 2 * BATCH

# Tolerance on max |served - reference| / max |reference| per request.  The
# served path runs the stem conv, the head, the classifier and the SE MLPs
# as XLA ops at the TPU's default matmul precision (one bf16 pass, about
# 2^-9 relative rounding per product), and that drift compounds through
# the 16-block chain; the reference runs every contraction at "highest".
# 5e-2 of the logit scale bounds that rounding with margin, while a wrong
# tap, stride, pad, mask or channel block moves logits by O(1) of it.
REL_TOL = 5e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded (2, 2)-mesh phase")
    return ap.parse_args(argv)


def make_requests(rng, n: int):
    """``n`` (H, W, 3) images with sides in [64, 224]; the first is a full
    224 x 224 so the bucket's whole extent is exercised."""
    import numpy as np
    sides = rng.integers(64, BUCKET + 1, size=(n, 2))
    sides[0] = (BUCKET, BUCKET)
    return [rng.standard_normal((int(h), int(w), 3)).astype(np.float32)
            for h, w in sides]


def padded_batch(images):
    """The images zero-padded into the bucket, as the engine packs them."""
    import numpy as np
    out = np.zeros((len(images), BUCKET, BUCKET, 3), np.float32)
    for i, im in enumerate(images):
        out[i, :im.shape[0], :im.shape[1]] = im
    return out


def reference_logits(params, images, cfg):
    """Plain float32 B0: XLA stem conv, ``kernels.ref.mbconv_ref`` per
    block (+ identity residual), head, pool, classifier — no Pallas, no
    schedule, no plan.  Trace it under ``default_matmul_precision``."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import mbconv_ref
    from repro.models.mbconv import effnet_block_specs

    x = jax.lax.conv_general_dilated(
        images, params["stem"], (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x = jax.nn.silu(x)
    for i, sp in enumerate(effnet_block_specs(cfg)):
        p = params[f"block{i}"]
        expands = "exp" in p
        y = mbconv_ref(
            x, p["exp"] if expands else jnp.eye(sp.c_mid, dtype=x.dtype),
            p["dw"], p["se_w1"], p["se_b1"], p["se_w2"], p["se_b2"],
            p["proj"], stride=sp.s, padding="SAME",
            exp_act="silu" if expands else None, dw_act="silu",
            se_act="silu", gate_act="sigmoid")
        x = y + x if sp.has_residual else y
    x = jax.nn.silu(jnp.einsum("bhwc,cd->bhwd", x, params["head"]))
    return x.mean(axis=(1, 2)) @ params["cls_w"] + params["cls_b"]


def check_logits(served, ref, what: str) -> float:
    import numpy as np
    if served.shape != ref.shape:
        fail(f"{what}: logits shape {served.shape} != reference {ref.shape}")
    if not np.all(np.isfinite(served)):
        fail(f"{what}: non-finite logits")
    worst = 0.0
    for i, (got, want) in enumerate(zip(served, ref)):
        scale = float(np.max(np.abs(want)))
        if scale == 0.0:
            fail(f"{what}: request {i} has an all-zero reference")
        worst = max(worst, float(np.max(np.abs(got - want))) / scale)
    print(f"chip_smoke: {what}: max |served - reference| / max |reference|"
          f" = {worst:.3e} (tolerance {REL_TOL:g})")
    if worst > REL_TOL:
        fail(f"{what}: logits disagree with the float32 reference "
             f"({worst:.3e} > {REL_TOL:g})")
    return worst


def main(argv=None) -> int:
    args = parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, str(src))

    import jax
    import numpy as np
    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU, but JAX found platform {dev.platform!r} "
             f"({dev.device_kind})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX found {len(devices)} device(s)")
    cache_dir = enable_compile_cache()
    cached = len(list(Path(cache_dir).iterdir())) \
        if Path(cache_dir).is_dir() else 0
    print(f"chip_smoke: {len(devices)} x {dev.device_kind}, compile cache "
          f"{cache_dir} ({cached} entries before this run)")

    from repro.core import telemetry
    from repro.core.autotune import set_schedule_cache_dir
    from repro.models.mbconv import EffNetConfig, efficientnet_b0_def
    from repro.models.param import materialize
    from repro.serve.vision import VisionEngine, VisionServeConfig

    set_schedule_cache_dir(None)     # solve every schedule from the model
    cfg = EffNetConfig()                       # width 1.0, 1000 classes
    params = materialize(efficientnet_b0_def(cfg),
                         jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    scfg = VisionServeConfig(resolutions=(BUCKET,), batch_size=BATCH)
    telemetry.reset()

    mesh = None
    if args.chips == 4:
        from repro.compat import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
    images = make_requests(rng, N_REQUESTS)
    engine = VisionEngine(params, cfg, scfg, mesh=mesh)
    for im in images:
        if engine.submit(im) is None:
            fail("a request was shed")

    results = []
    batch_s = []
    while engine.pending():
        t0 = time.perf_counter()
        results.extend(engine.step())
        batch_s.append(time.perf_counter() - t0)
    if len(results) != N_REQUESTS:
        fail(f"served {len(results)} of {N_REQUESTS} requests")
    print(f"chip_smoke: first batch {batch_s[0]:.2f} s (trace + compile + "
          f"run); smoke latency per later batch: "
          + (", ".join(f"{s * 1e3:.1f} ms" for s in batch_s[1:]) or "n/a"))

    tele = telemetry.get_telemetry()
    traces = tele.get(f"serve.trace.r{BUCKET}")
    if traces != 1:
        fail(f"bucket {BUCKET} traced {traces} times, expected 1")
    if mesh is not None:
        dispatched = tele.get("sharded.dispatch.mbconv")
        print(f"chip_smoke: sharded.dispatch.mbconv = {dispatched:g}")
        if not dispatched > 0:
            fail("no MBConv block dispatched through the sharded wrappers")
    else:
        sample = jax.numpy.zeros((BATCH, BUCKET, BUCKET, 3), np.float32)
        hlo = engine._apply_for(BUCKET).lower(params, sample).compile() \
            .as_text()
        n_kernels = hlo.count("tpu_custom_call")
        print(f"chip_smoke: compiled apply holds {n_kernels} "
              f"tpu_custom_call sites")
        if n_kernels == 0:
            fail("no Mosaic kernel in the compiled apply (interpreted?)")

    served = np.stack([r.logits for r in sorted(results,
                                                key=lambda r: r.rid)])
    ref_params = jax.device_put(params, dev)
    ref_in = jax.device_put(padded_batch(images), dev)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(reference_logits, static_argnums=2)(
            ref_params, ref_in, cfg))
    what = "(2, 2) mesh vs one-device reference" if mesh is not None \
        else "one chip vs reference"
    check_logits(served, ref, what)

    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"chip_smoke: device 0 peak_bytes_in_use = "
              f"{stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
