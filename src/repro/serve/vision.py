"""Batched vision serving engine over the fused EfficientNet pipeline.

The LM engine (``serve.engine``) buckets requests by prompt LENGTH; the
vision engine generalizes the same BIG/LITTLE admission idea to image
RESOLUTION: mixed 224/384/512 requests are admitted into per-resolution
buckets and launched as shape-stable jitted batches (one trace per bucket,
never per request), through ``efficientnet_b0_apply`` with the
network-level layout plan (``core.autotune.get_network_plan``) solved ONCE
per bucket and threaded into every launch.

Three serving concerns the benchmark harness never had to answer live
here:

* **Admission + load shedding** — a bounded request queue; ``submit``
  refuses work above the bound (or images above the largest bucket) and
  counts every rejection, so overload is measured instead of unbounded.
* **Traffic telemetry where it happens** — every launched batch charges
  per-(layer x shape-class) counters with the MODELED bytes of the exact
  schedules the blocks run (the plan is passed into the model call, so
  counter bytes and executed schedules cannot drift): the paper's
  "buffer traffic dominates" argument, surfaced per layer while serving.
  ``benchmarks/serve_report.py`` tabulates the counters as a top-N
  bottleneck report and gates the reconciliation.
* **Latency percentiles** — per-request latencies from blocked timings
  (``jax.block_until_ready``, the ``telemetry.measure`` discipline)
  recorded as telemetry series alongside queue wait times.

Admission is a TWO-LEVEL FIFO: ``submit(image, priority=1)`` places a
request in the priority lane, which ``step`` drains ahead of the normal
lane (FIFO within each lane; the batch back-fills from the normal lane's
same bucket).  Shedding is unchanged — the queue bound applies to the
COMBINED depth, so priority requests cannot starve the shed accounting.

Every bucket's plan comes from the network-level solve, which now
includes the cross-block ``overlap`` axis: boundaries the DP proves
pipelinable execute pass 2 of block *i* overlapped with pass 1 of block
*i+1* (``models.blockgraph`` validates the buffer hazards at lowering),
so serving inherits the pipelined chain latency without any engine code
knowing about it.  ``serve.pipelined_boundaries.r<res>`` records how
many boundaries of the bucket's plan pipeline.

Counter naming (shape-class first, then layer):

    serve.admitted / serve.admitted.priority
    serve.shed.queue_full / serve.shed.oversize
    serve.batches.r<res> / serve.requests.r<res> / serve.pad_slots.r<res>
    serve.bytes.r<res>.<layer>       modeled bytes moved (layer = stem,
                                     block00..blockNN, boundaries)
    serve.collective.r<res>.<layer>  modeled interconnect bytes
    serve.trace.r<res>               trace-time: retrace counter
    serve.pipelined_boundaries.r<res>  plan-time: solved overlap count

Series: ``serve.queue_wait_s``, ``serve.latency_s``.

Spans: ``serve.batch.r<res>`` around each launch, with the children
``serve.pack`` (host batch build), ``serve.transfer`` (host to device),
``serve.compute`` (the blocked call) and ``serve.copy_back`` (logits to
the host); under a profiler trace they name the device's idle gaps.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry
from ..core.autotune import NetworkPlan, get_network_plan
from ..models.mbconv import (
    EffNetConfig,
    effnet_block_specs,
    effnet_chain_rows,
    efficientnet_b0_apply,
)

__all__ = [
    "VisionEngine",
    "VisionRequest",
    "VisionResult",
    "VisionServeConfig",
    "layer_names",
]

STEM_STRIDE = 2      # the B0 stem conv halves the spatial dims


@dataclasses.dataclass(frozen=True)
class VisionServeConfig:
    """Admission policy of one vision serving engine.

    ``resolutions`` are the square admission buckets, ascending; a request
    joins the smallest bucket its longest side fits (zero-padded up to the
    bucket — no resampling dependency), and anything above the largest
    bucket is shed.  ``batch_size`` is the shape-stable pack per launch
    (short packs pad with zero images — the padding slots are counted).
    ``max_queue`` bounds the admission queue; ``submit`` sheds above it.
    """

    resolutions: Tuple[int, ...] = (224, 384, 512)
    batch_size: int = 8
    max_queue: int = 64

    def __post_init__(self):
        if not self.resolutions:
            raise ValueError("need at least one resolution bucket")
        if list(self.resolutions) != sorted(set(self.resolutions)):
            raise ValueError(
                f"resolutions must be strictly ascending, "
                f"got {self.resolutions}")
        if min(self.resolutions) < STEM_STRIDE:
            raise ValueError(f"resolutions must be >= {STEM_STRIDE}")
        if self.batch_size < 1 or self.max_queue < 1:
            raise ValueError("batch_size and max_queue must be >= 1")


@dataclasses.dataclass
class VisionRequest:
    """One admitted request waiting in (or leaving) the queue."""

    rid: int
    image: np.ndarray
    bucket: int                  # admission resolution
    t_submit: float
    priority: int = 0            # > 0 = priority lane (drained first)


@dataclasses.dataclass
class VisionResult:
    """One served request: logits plus the serving story around them."""

    rid: int
    bucket: int
    logits: np.ndarray
    latency_s: float             # submit -> blocked batch completion
    queue_wait_s: float          # submit -> batch launch
    traffic_bytes: float         # this request's share of the batch's
    # modeled end-to-end bytes (the full padded batch is charged to the
    # real requests riding it, so padding waste shows up per request)


def layer_names(n_blocks: int) -> Tuple[str, ...]:
    """Per-launch traffic-counter layer labels, chain order."""
    return ("stem",) + tuple(f"block{i:02d}" for i in range(n_blocks)) \
        + ("boundaries",)


class VisionEngine:
    """Admission-bucketed batched inference over the fused B0 pipeline.

    ``submit()`` admits (or sheds) one image; ``step()`` launches ONE
    shape-stable batch — the oldest waiter's bucket, filled FIFO from that
    bucket up to ``batch_size``; ``drain()`` steps until the queue is
    empty.  Every launch reuses the bucket's jitted entry point and its
    once-solved ``NetworkPlan`` (``plan_for``), so steady-state serving
    never re-traces and never re-solves.
    """

    def __init__(self, params, cfg: EffNetConfig = EffNetConfig(),
                 serve_cfg: Optional[VisionServeConfig] = None,
                 mesh=None, kcfg=None):
        self.params = params
        self.cfg = cfg
        self.scfg = serve_cfg or VisionServeConfig()
        self.mesh = mesh
        if kcfg is None:
            from ..configs.base import kernel_config
            kcfg = kernel_config()
        self.kcfg = kcfg
        self.specs = effnet_block_specs(cfg)
        self._queue: Deque[VisionRequest] = deque()
        self._pqueue: Deque[VisionRequest] = deque()
        self._next_rid = 0
        self._plans: Dict[int, NetworkPlan] = {}
        self._applies: Dict[int, object] = {}

    # -- admission -----------------------------------------------------------

    def bucket_for(self, h: int, w: int) -> Optional[int]:
        """Smallest resolution bucket the image fits; None = oversize."""
        side = max(h, w)
        for res in self.scfg.resolutions:
            if side <= res:
                return res
        return None

    def submit(self, image: np.ndarray, priority: int = 0) -> Optional[int]:
        """Admit one (H, W, 3) image.  Returns the request id, or None
        when the request is SHED (queue at bound, or image above the
        largest bucket) — every shed increments its rejection counter.

        ``priority > 0`` admits into the priority lane, which ``step``
        drains ahead of the normal lane.  The queue bound covers BOTH
        lanes combined — priority admission never bypasses shedding, it
        only reorders service among the admitted."""
        image = np.asarray(image)
        if image.ndim != 3 or image.shape[-1] != 3:
            raise ValueError(f"expected an (H, W, 3) image, "
                             f"got shape {image.shape}")
        bucket = self.bucket_for(image.shape[0], image.shape[1])
        if bucket is None:
            telemetry.counter("serve.shed.oversize")
            return None
        if self.pending() >= self.scfg.max_queue:
            telemetry.counter("serve.shed.queue_full")
            return None
        rid = self._next_rid
        self._next_rid += 1
        rq = VisionRequest(rid=rid, image=image, bucket=bucket,
                           t_submit=time.perf_counter(), priority=priority)
        (self._pqueue if priority > 0 else self._queue).append(rq)
        telemetry.counter("serve.admitted")
        if priority > 0:
            telemetry.counter("serve.admitted.priority")
        return rid

    def pending(self) -> int:
        return len(self._pqueue) + len(self._queue)

    @property
    def shed(self) -> int:
        """Total requests shed so far (both rejection counters)."""
        t = telemetry.get_telemetry()
        return int(t.get("serve.shed.queue_full")
                   + t.get("serve.shed.oversize"))

    # -- per-bucket plan + jitted entry --------------------------------------

    def _mesh_shape(self) -> Tuple[int, int]:
        if self.mesh is None:
            return (1, 1)
        from ..kernels import conv_mesh_shape
        return conv_mesh_shape(self.mesh)

    def plan_for(self, res: int) -> NetworkPlan:
        """The bucket's network-level layout plan: solved once per
        resolution (chain rows start at the stem-output dims), reused by
        every batch of that bucket — and threaded into the model call, so
        the schedules priced here are the schedules that run."""
        if res not in self._plans:
            stem_hw = -(-res // STEM_STRIDE)
            rows = effnet_chain_rows(self.specs, stem_hw, stem_hw)
            plan = get_network_plan(
                rows, self.scfg.batch_size, self._mesh_shape(),
                dtype_bytes=jnp.dtype(self.cfg.dtype).itemsize,
                se_ratio=self.cfg.se_ratio)
            self._plans[res] = plan
            # solve-time, like the plan itself: how many boundaries of
            # this bucket's chain execute pipelined (pass-2 ∥ pass-1)
            telemetry.counter(f"serve.pipelined_boundaries.r{res}",
                              len(plan.pipelined_boundaries))
        return self._plans[res]

    def modeled_layer_bytes(self, res: int) -> Dict[str, Tuple[int, int]]:
        """Per-LAUNCH modeled traffic of one bucket: layer label ->
        (total bytes, collective bytes).  The exact increments every
        launched batch of this bucket adds to its counters — the
        reconciliation contract ``serve_report``/tests gate on."""
        plan = self.plan_for(res)
        out: Dict[str, Tuple[int, int]] = {"stem": (plan.stem_bytes, 0)}
        for i, bp in enumerate(plan.blocks):
            out[f"block{i:02d}"] = (bp.schedule.total_bytes,
                                    bp.schedule.collective_bytes)
        out["boundaries"] = (plan.boundary_words * plan.dtype_bytes, 0)
        return out

    def _apply_for(self, res: int):
        if res not in self._applies:
            plan = self.plan_for(res)
            cfg, kcfg, mesh = self.cfg, self.kcfg, self.mesh

            def apply(params, images):
                # trace-time increment (telemetry's documented jit
                # semantics): fires once per COMPILATION, so this counter
                # staying at 1 per bucket IS the no-per-request-retrace
                # guarantee the admission design makes
                telemetry.counter(f"serve.trace.r{res}")
                return efficientnet_b0_apply(params, images, cfg, kcfg,
                                             mesh=mesh, plan=plan)

            self._applies[res] = jax.jit(apply)
        return self._applies[res]

    # -- serving -------------------------------------------------------------

    def step(self) -> List[VisionResult]:
        """Launch ONE batch: the oldest PRIORITY waiter's bucket (falling
        back to the oldest normal waiter), filled FIFO from that bucket —
        priority lane first, then back-filled from the normal lane — up
        to ``batch_size`` (short packs zero-pad)."""
        if not self._pqueue and not self._queue:
            return []
        head = self._pqueue[0] if self._pqueue else self._queue[0]
        res = head.bucket
        take: List[VisionRequest] = []
        for lane_name in ("_pqueue", "_queue"):
            lane: Deque[VisionRequest] = getattr(self, lane_name)
            keep: Deque[VisionRequest] = deque()
            for rq in lane:
                if rq.bucket == res and len(take) < self.scfg.batch_size:
                    take.append(rq)
                else:
                    keep.append(rq)
            setattr(self, lane_name, keep)
        return self._launch(res, take)

    def drain(self) -> List[VisionResult]:
        """Step until both lanes are empty; results in completion order."""
        out: List[VisionResult] = []
        while self._pqueue or self._queue:
            out.extend(self.step())
        return out

    def _launch(self, res: int, reqs: List[VisionRequest]
                ) -> List[VisionResult]:
        plan = self.plan_for(res)
        fn = self._apply_for(res)
        with telemetry.span(f"serve.batch.r{res}"):
            with telemetry.span("serve.pack"):
                batch = np.zeros((self.scfg.batch_size, res, res, 3),
                                 np.float32)
                for row, rq in enumerate(reqs):
                    h, w = rq.image.shape[:2]
                    batch[row, :h, :w, :] = rq.image
            t_launch = time.perf_counter()
            with telemetry.span("serve.transfer"):
                x = jnp.asarray(batch)
            with telemetry.span("serve.compute"):
                logits = jax.block_until_ready(fn(self.params, x))
            t_done = time.perf_counter()
            with telemetry.span("serve.copy_back"):
                arr = np.asarray(logits)

        telemetry.counter(f"serve.batches.r{res}")
        telemetry.counter(f"serve.requests.r{res}", len(reqs))
        telemetry.counter(f"serve.pad_slots.r{res}",
                          self.scfg.batch_size - len(reqs))
        for layer, (total, coll) in self.modeled_layer_bytes(res).items():
            telemetry.counter(f"serve.bytes.r{res}.{layer}", total)
            telemetry.counter(f"serve.collective.r{res}.{layer}", coll)

        share = plan.total_bytes / max(1, len(reqs))
        results = []
        for row, rq in enumerate(reqs):
            latency = t_done - rq.t_submit
            wait = t_launch - rq.t_submit
            telemetry.record("serve.latency_s", latency)
            telemetry.record("serve.queue_wait_s", wait)
            results.append(VisionResult(
                rid=rq.rid, bucket=res, logits=arr[row],
                latency_s=latency, queue_wait_s=wait, traffic_bytes=share))
        return results

    # -- observability -------------------------------------------------------

    def latency_percentiles(self, qs: Sequence[float] = (50, 90, 99)
                            ) -> Dict[str, float]:
        """Nearest-rank percentiles over every served request's blocked
        latency (the ``serve.latency_s`` series)."""
        return telemetry.percentiles(telemetry.series("serve.latency_s"), qs)
