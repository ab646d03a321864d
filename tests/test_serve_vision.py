"""Vision serving engine: resolution-bucket admission, shape-stable
batches (one trace per bucket — asserted via the trace-time counter),
load-shedding at the queue bound, and exact reconciliation of telemetry
byte counters against the solved plans' modeled traffic."""

import jax
import numpy as np
import pytest

from repro.configs.efficientnet_b0 import efficientnet_b0_smoke
from repro.core import telemetry
from repro.models.mbconv import efficientnet_b0_def
from repro.models.param import materialize
from repro.serve import VisionEngine, VisionServeConfig
from repro.serve.vision import layer_names

RES = (16, 24, 32)


@pytest.fixture(scope="module")
def engine_parts():
    cfg = efficientnet_b0_smoke(width_mult=0.125, num_classes=4)
    params = materialize(efficientnet_b0_def(cfg), jax.random.key(0))
    return cfg, params


def _engine(engine_parts, **kw):
    cfg, params = engine_parts
    kw.setdefault("resolutions", RES)
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_queue", 8)
    return VisionEngine(params, cfg, VisionServeConfig(**kw))


def _img(rng, side):
    return rng.random((side, side, 3), np.float32)


def test_bucket_admission(engine_parts):
    telemetry.reset()
    eng = _engine(engine_parts)
    assert eng.bucket_for(14, 9) == 16
    assert eng.bucket_for(16, 16) == 16
    assert eng.bucket_for(17, 4) == 24     # longest side picks the bucket
    assert eng.bucket_for(32, 32) == 32
    assert eng.bucket_for(33, 1) is None   # above the largest bucket

    rng = np.random.default_rng(0)
    assert eng.submit(_img(rng, 12)) == 0
    assert eng.submit(_img(rng, 40)) is None          # oversize -> shed
    assert telemetry.get_telemetry().get("serve.shed.oversize") == 1
    with pytest.raises(ValueError):
        eng.submit(rng.random((8, 8), np.float32))    # not (H, W, 3)


def test_load_shedding_at_queue_bound(engine_parts):
    telemetry.reset()
    eng = _engine(engine_parts, max_queue=3)
    rng = np.random.default_rng(1)
    rids = [eng.submit(_img(rng, 16)) for _ in range(5)]
    assert rids[:3] == [0, 1, 2]
    assert rids[3:] == [None, None]        # queue at bound -> shed
    t = telemetry.get_telemetry()
    assert t.get("serve.shed.queue_full") == 2
    assert t.get("serve.admitted") == 3
    assert eng.shed == 2
    # draining frees the queue: admission resumes
    eng.drain()
    assert eng.submit(_img(rng, 16)) == 3


def test_mixed_stream_shape_stable_batches(engine_parts):
    """Mixed 16/24/32 submissions must compile ONCE per bucket, never per
    request or per batch: the trace-time counter inside each bucket's
    jitted apply is the retrace detector."""
    telemetry.reset()
    eng = _engine(engine_parts)
    rng = np.random.default_rng(2)
    sides = (14, 16, 24, 20, 32, 30, 12)   # buckets: 3x r16, 2x r24, 2x r32
    for side in sides:
        assert eng.submit(_img(rng, side)) is not None
    results = eng.drain()
    assert eng.pending() == 0
    assert [r.rid for r in sorted(results, key=lambda r: r.rid)] \
        == list(range(len(sides)))
    assert all(r.logits.shape == (4,) for r in results)
    assert all(r.latency_s >= r.queue_wait_s >= 0 for r in results)

    t = telemetry.get_telemetry()
    # r16 takes sides 14,16,12 (2 batches of batch_size=2), r24 takes
    # 24,20 (1 batch), r32 takes 32,30 (1 batch)
    assert t.get("serve.batches.r16") == 2
    assert t.get("serve.batches.r24") == 1
    assert t.get("serve.batches.r32") == 1
    assert t.get("serve.pad_slots.r16") == 1
    # THE shape-stability assertion: one compilation per bucket
    for res in RES:
        assert t.get(f"serve.trace.r{res}") == 1, res

    # FIFO within a bucket, batches keyed by the oldest waiter
    by_rid = {r.rid: r for r in results}
    assert [by_rid[i].bucket for i in range(7)] \
        == [16, 16, 24, 24, 32, 32, 16]


def test_counters_reconcile_with_modeled_traffic(engine_parts):
    """The acceptance gate: every (bucket, layer) byte counter equals
    n_batches x the solved plan's modeled bytes for that layer, and the
    per-layer rows sum to ``NetworkPlan.total_bytes`` — the engine
    charges exactly what ``perfmodel``'s ShardedTraffic prices."""
    telemetry.reset()
    eng = _engine(engine_parts)
    rng = np.random.default_rng(3)
    for side in (16, 16, 16, 24, 32, 32):
        eng.submit(_img(rng, side))
    eng.drain()

    t = telemetry.get_telemetry()
    n_layers = len(layer_names(len(eng.specs)))
    for res in RES:
        nb = t.get(f"serve.batches.r{res}")
        assert nb >= 1
        modeled = eng.modeled_layer_bytes(res)
        assert len(modeled) == n_layers
        for layer, (total, coll) in modeled.items():
            assert t.get(f"serve.bytes.r{res}.{layer}") == nb * total
            assert t.get(f"serve.collective.r{res}.{layer}") == nb * coll
        plan = eng.plan_for(res)
        assert sum(tb for tb, _ in modeled.values()) == plan.total_bytes


def test_request_traffic_shares_sum_to_plan(engine_parts):
    telemetry.reset()
    eng = _engine(engine_parts)
    rng = np.random.default_rng(4)
    for side in (16, 12, 24):              # one full r16 batch + short r24
        eng.submit(_img(rng, side))
    results = eng.drain()
    r16 = [r for r in results if r.bucket == 16]
    r24 = [r for r in results if r.bucket == 24]
    assert sum(r.traffic_bytes for r in r16) \
        == pytest.approx(eng.plan_for(16).total_bytes)
    # a lone rider on a padded batch is charged the WHOLE batch
    assert r24[0].traffic_bytes == pytest.approx(
        eng.plan_for(24).total_bytes)


def test_plan_solved_once_per_bucket(engine_parts):
    """Steady state never re-solves: the autotune counters must show one
    network-plan solve per bucket and reuses for every later launch."""
    telemetry.reset()
    eng = _engine(engine_parts, resolutions=(16,))
    rng = np.random.default_rng(5)
    for _ in range(6):
        eng.submit(_img(rng, 16))
    eng.drain()
    t = telemetry.get_telemetry()
    assert t.get("serve.batches.r16") == 3
    # plan_for caches in-engine; the underlying get_network_plan fires
    # once on the first launch path (solve OR reuse from another test's
    # lru cache) — what matters is the engine asked autotune only once
    assert (t.get("autotune.network_plan.solve")
            + t.get("autotune.network_plan.reuse")) == 1


def test_latency_series_and_percentiles(engine_parts):
    telemetry.reset()
    eng = _engine(engine_parts, resolutions=(16,))
    rng = np.random.default_rng(6)
    for _ in range(4):
        eng.submit(_img(rng, 16))
    eng.drain()
    assert len(telemetry.series("serve.latency_s")) == 4
    pct = eng.latency_percentiles()
    assert set(pct) == {"p50", "p90", "p99"}
    assert 0 < pct["p50"] <= pct["p90"] <= pct["p99"]
    snap = telemetry.get_telemetry().snapshot()
    assert snap["series"]["serve.queue_wait_s"]["count"] == 4


def test_priority_admission_two_level_fifo(engine_parts):
    """Priority requests are served ahead of earlier normal requests
    (FIFO within each lane; the batch back-fills from the normal lane's
    same bucket), and the priority counter tracks them."""
    telemetry.reset()
    eng = _engine(engine_parts, resolutions=(16,), batch_size=2)
    rng = np.random.default_rng(7)
    r0 = eng.submit(_img(rng, 16))                       # normal
    r1 = eng.submit(_img(rng, 16))                       # normal
    r2 = eng.submit(_img(rng, 16), priority=1)           # priority
    r3 = eng.submit(_img(rng, 16), priority=1)           # priority
    assert [r0, r1, r2, r3] == [0, 1, 2, 3]
    # batch 1 = both priority requests, ahead of the earlier normal two
    first = eng.step()
    assert sorted(r.rid for r in first) == [2, 3]
    second = eng.step()
    assert sorted(r.rid for r in second) == [0, 1]
    t = telemetry.get_telemetry()
    assert t.get("serve.admitted") == 4
    assert t.get("serve.admitted.priority") == 2


def test_priority_batch_backfills_from_normal_lane(engine_parts):
    """A lone priority request rides with same-bucket normal waiters —
    the priority lane picks the bucket, the normal lane fills the pack."""
    telemetry.reset()
    eng = _engine(engine_parts, batch_size=2)
    rng = np.random.default_rng(8)
    eng.submit(_img(rng, 24))                            # normal, r24
    eng.submit(_img(rng, 16))                            # normal, r16
    eng.submit(_img(rng, 16), priority=1)                # priority, r16
    batch = eng.step()
    # the priority waiter's bucket (16) launches first, back-filled with
    # the normal r16 request; the older normal r24 request waits
    assert sorted(r.rid for r in batch) == [1, 2]
    assert all(r.bucket == 16 for r in batch)
    assert eng.pending() == 1
    rest = eng.drain()
    assert [r.rid for r in rest] == [0]


def test_priority_does_not_bypass_shedding(engine_parts):
    """The queue bound covers both lanes combined: priority admission
    reorders service among the admitted, never the shed accounting."""
    telemetry.reset()
    eng = _engine(engine_parts, resolutions=(16,), max_queue=2)
    rng = np.random.default_rng(9)
    assert eng.submit(_img(rng, 16)) == 0
    assert eng.submit(_img(rng, 16), priority=1) == 1
    assert eng.submit(_img(rng, 16), priority=1) is None  # bound -> shed
    assert eng.submit(_img(rng, 16)) is None
    t = telemetry.get_telemetry()
    assert t.get("serve.shed.queue_full") == 2
    assert eng.shed == 2
    assert eng.pending() == 2


def test_launch_spans_split_the_batch(engine_parts):
    """Each launch is the span ``serve.batch.r<res>`` with one child span
    per host phase: pack, transfer, compute, copy back."""
    telemetry.reset()
    eng = _engine(engine_parts, resolutions=(16,))
    rng = np.random.default_rng(7)
    for _ in range(4):
        eng.submit(_img(rng, 16))
    eng.drain()
    spans = telemetry.snapshot()["spans"]
    children = ("serve.pack", "serve.transfer", "serve.compute",
                "serve.copy_back")
    assert spans["serve.batch.r16"]["count"] == 2
    for name in children:
        assert spans[name]["count"] == 2
    assert spans["serve.batch.r16"]["total_s"] >= sum(
        spans[name]["total_s"] for name in children)
    assert "serve.queue_depth" not in telemetry.snapshot()["series"]


def test_pipelined_boundaries_counter(engine_parts):
    """Solving a bucket's plan records the solved overlap count — 0 on
    the degenerate (1,1) mesh is fine; what matters is the counter fires
    once per bucket at solve time and matches the plan."""
    telemetry.reset()
    eng = _engine(engine_parts, resolutions=(16,))
    plan = eng.plan_for(16)
    t = telemetry.get_telemetry()
    assert t.get("serve.pipelined_boundaries.r16") \
        == len(plan.pipelined_boundaries)
    eng.plan_for(16)                                     # cached: no re-count
    assert t.get("serve.pipelined_boundaries.r16") \
        == len(plan.pipelined_boundaries)


def test_serve_config_validation():
    with pytest.raises(ValueError):
        VisionServeConfig(resolutions=())
    with pytest.raises(ValueError):
        VisionServeConfig(resolutions=(32, 16))      # not ascending
    with pytest.raises(ValueError):
        VisionServeConfig(resolutions=(16, 16, 24))  # duplicate
    with pytest.raises(ValueError):
        VisionServeConfig(resolutions=(16,), batch_size=0)
