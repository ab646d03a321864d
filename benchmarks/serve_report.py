"""Vision serving report: drive a mixed-resolution request stream through
``serve.VisionEngine`` and tabulate what the telemetry counters saw.

Output sections:

* **top-N (layer x shape-class) traffic rows** — the serving-time
  bottleneck table: for every resolution bucket and chain layer, the
  bytes the engine charged while serving (counter value = n_batches x
  the solved plan's modeled bytes for that layer), sorted descending.
* **per-bucket summary** — batches / requests / pad slots / one-trace
  check per bucket, plus admission + shedding totals.
* **latency** — p50/p90/p99 over per-request blocked timings, and queue
  wait percentiles.

Exit status is the CI gate: nonzero unless (a) the table is non-empty,
(b) every bucket compiled exactly once (trace counter == 1), and
(c) every served layer's counter bytes reconcile EXACTLY with
n_batches x the solved schedule's modeled bytes — the engine may not
drift from ``perfmodel``'s ShardedTraffic pricing.

``--smoke`` serves CI-sized buckets (28/48/64, a 10-class B0 at
width_mult 0.25) so the report runs in interpret mode in seconds; without
it the engine serves the published EfficientNet-B0 (width 1.0, 1000
classes) at the paper sizes (224/384/512).
"""

from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.efficientnet_b0 import (
    efficientnet_b0,
    efficientnet_b0_smoke,
)
from repro.core import telemetry
from repro.models.mbconv import efficientnet_b0_def
from repro.models.param import materialize
from repro.serve import VisionEngine, VisionServeConfig
from repro.serve.vision import layer_names


def _parse_resolutions(text: str):
    return tuple(int(tok) for tok in text.split(",") if tok.strip())


def build_stream(resolutions, n_requests: int, seed: int):
    """A mixed stream: sides drawn uniformly over admission-valid sizes,
    skewed so every bucket gets traffic (round-robin over buckets, with
    the side jittered below each bucket bound)."""
    rng = np.random.default_rng(seed)
    lo = 2
    sides = []
    for i in range(n_requests):
        res = resolutions[i % len(resolutions)]
        floor = resolutions[i % len(resolutions) - 1] + 1 \
            if i % len(resolutions) else lo
        sides.append(int(rng.integers(floor, res + 1)))
    rng.shuffle(sides)
    return [rng.random((s, s, 3), dtype=np.float32) for s in sides]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized buckets (28/48/64, width_mult 0.25)")
    ap.add_argument("--resolutions", type=_parse_resolutions, default=None,
                    help="comma list of admission buckets (ascending)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--width-mult", type=float, default=None)
    ap.add_argument("--top", type=int, default=12,
                    help="rows in the (layer x shape-class) table")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.resolutions is not None:
        resolutions = args.resolutions
    elif args.smoke:
        resolutions = (28, 48, 64)
    else:
        resolutions = (224, 384, 512)
    width = args.width_mult if args.width_mult is not None \
        else (0.25 if args.smoke else 1.0)

    enable_compile_cache()
    telemetry.reset()
    cfg = (efficientnet_b0_smoke(width_mult=width, num_classes=10)
           if args.smoke else efficientnet_b0(width_mult=width))
    params = materialize(efficientnet_b0_def(cfg), jax.random.key(args.seed))
    eng = VisionEngine(params, cfg, VisionServeConfig(
        resolutions=resolutions, batch_size=args.batch_size,
        max_queue=args.max_queue))

    stream = build_stream(resolutions, args.requests, args.seed)
    admitted = sum(eng.submit(img) is not None for img in stream)
    results = eng.drain()
    t = telemetry.get_telemetry()

    # -- top-N (layer x shape-class) traffic table --------------------------
    rows = []
    for res in resolutions:
        nb = int(t.get(f"serve.batches.r{res}"))
        if not nb:
            continue
        for layer in layer_names(len(eng.specs)):
            rows.append((
                f"r{res}", layer,
                int(t.get(f"serve.bytes.r{res}.{layer}")),
                int(t.get(f"serve.collective.r{res}.{layer}")),
                nb,
            ))
    rows.sort(key=lambda r: -r[2])
    print(f"# serve_report: {len(results)} served / {admitted} admitted / "
          f"{eng.shed} shed; buckets={','.join(map(str, resolutions))} "
          f"batch={args.batch_size} width={width}")
    print("shape_class,layer,bytes,collective_bytes,batches")
    for r in rows[:args.top]:
        print(",".join(map(str, r)))

    # -- per-bucket summary -------------------------------------------------
    print("\nbucket,batches,requests,pad_slots,traces")
    for res in resolutions:
        print(f"r{res},{int(t.get(f'serve.batches.r{res}'))},"
              f"{int(t.get(f'serve.requests.r{res}'))},"
              f"{int(t.get(f'serve.pad_slots.r{res}'))},"
              f"{int(t.get(f'serve.trace.r{res}'))}")
    print(f"shed_queue_full={int(t.get('serve.shed.queue_full'))} "
          f"shed_oversize={int(t.get('serve.shed.oversize'))}")

    # -- latency ------------------------------------------------------------
    lat = eng.latency_percentiles()
    wait = telemetry.percentiles(telemetry.series("serve.queue_wait_s"))
    print("\nlatency_s:", " ".join(f"{k}={v:.4f}"
                                   for k, v in sorted(lat.items())))
    print("queue_wait_s:", " ".join(f"{k}={v:.4f}"
                                    for k, v in sorted(wait.items())))

    # -- gates --------------------------------------------------------------
    ok = True
    if not rows:
        print("GATE FAIL: empty traffic table (nothing served?)")
        ok = False
    for res in resolutions:
        nb = int(t.get(f"serve.batches.r{res}"))
        if not nb:
            continue
        if t.get(f"serve.trace.r{res}") != 1:
            print(f"GATE FAIL: r{res} retraced "
                  f"({int(t.get(f'serve.trace.r{res}'))} compilations)")
            ok = False
        modeled = eng.modeled_layer_bytes(res)
        for layer, (total, coll) in modeled.items():
            got = t.get(f"serve.bytes.r{res}.{layer}")
            if got != nb * total:
                print(f"GATE FAIL: r{res}.{layer} counter {int(got)} != "
                      f"{nb} x modeled {total}")
                ok = False
            got_c = t.get(f"serve.collective.r{res}.{layer}")
            if got_c != nb * coll:
                print(f"GATE FAIL: r{res}.{layer} collective {int(got_c)} "
                      f"!= {nb} x modeled {coll}")
                ok = False
        plan = eng.plan_for(res)
        if sum(tb for tb, _ in modeled.values()) != plan.total_bytes:
            print(f"GATE FAIL: r{res} layer rows do not sum to "
                  f"plan.total_bytes")
            ok = False
    print(f"\ngate: {'OK' if ok else 'FAIL'} — counters "
          f"{'reconcile exactly with' if ok else 'DRIFTED from'} "
          f"solved-schedule ShardedTraffic bytes")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
