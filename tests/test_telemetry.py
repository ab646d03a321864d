"""Telemetry subsystem: counters/spans, the canonical ``measure()`` harness
(including the exact call-count contract that fixes the old double-eval
warmup), trace-time counter semantics under jit, the ``BENCH_<host>.json``
schema round-trip, the trajectory differ's regression detection, and the
measured-calibration fit."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import telemetry
from repro.core.perfmodel import (
    PerfCoefficients,
    fit_perf_coefficients,
    predict_walltime_us,
)
from repro.core.telemetry import Measurement, Telemetry, measure
from repro.core.trajectory import (
    bench_filename,
    diff_bench,
    load_bench,
    rank_agreement,
    validate_bench,
    write_bench,
)

# ---------------------------------------------------------------------------
# counters + spans
# ---------------------------------------------------------------------------


def test_counter_accumulates_and_defaults_zero():
    t = Telemetry()
    assert t.get("x") == 0
    t.count("x")
    t.count("x", 2)
    t.count("y", 0.5)
    assert t.get("x") == 3
    assert t.get("y") == 0.5


def test_span_aggregates_count_total_min_max():
    t = Telemetry()
    for _ in range(3):
        with t.span("work"):
            pass
    st = t.span_stat("work")
    assert st.count == 3
    assert st.total_s >= st.max_s >= st.min_s >= 0
    assert t.span_stat("absent") is None


def test_span_records_on_exception():
    t = Telemetry()
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    assert t.span_stat("boom").count == 1


def test_nested_same_name_span_counts_outermost_only():
    t = Telemetry()
    with t.span("plan"):
        with t.span("plan"):
            with t.span("other"):
                pass
    assert t.span_stat("plan").count == 1
    assert t.span_stat("other").count == 1
    with t.span("plan"):                      # depth restored after exit
        pass
    assert t.span_stat("plan").count == 2


def test_span_depth_is_per_thread():
    import threading
    t = Telemetry()
    inner = threading.Event()
    done = threading.Event()

    def worker():
        inner.wait()
        with t.span("plan"):
            pass
        done.set()

    th = threading.Thread(target=worker)
    th.start()
    with t.span("plan"):
        inner.set()
        assert done.wait(10)
    th.join()
    assert t.span_stat("plan").count == 2


def test_span_writes_profiler_host_event(tmp_path):
    """Under a profiler trace a span is an event of its name on the
    ``/host:CPU`` plane, the plane the benchmark reads host spans from."""
    import glob
    from jax.profiler import ProfileData
    t = Telemetry()
    with jax.profiler.trace(str(tmp_path)):
        with t.span("telemetry.probe_span"):
            jax.block_until_ready(jnp.ones(4) + 1)
    found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert found
    data = ProfileData.from_file(found[0])
    names = {ev.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert "telemetry.probe_span" in names
    assert t.span_stat("telemetry.probe_span").count == 1


def test_snapshot_is_json_ready_and_reset_clears():
    import json

    t = Telemetry()
    t.count("a.b", 4)
    with t.span("s"):
        pass
    t.record("lat", 0.25)
    snap = t.snapshot()
    json.dumps(snap)                       # must serialize as-is
    assert snap["counters"] == {"a.b": 4}
    assert snap["spans"]["s"]["count"] == 1
    assert snap["series"]["lat"]["count"] == 1
    t.reset()
    assert t.snapshot() == {"counters": {}, "spans": {}, "series": {}}


def test_series_bounded_and_summarized():
    t = Telemetry()
    for v in range(telemetry.SERIES_CAP + 10):
        t.record("depth", v)
    vals = t.series("depth")
    assert len(vals) == telemetry.SERIES_CAP     # oldest samples dropped
    assert vals[-1] == telemetry.SERIES_CAP + 9
    summ = t.snapshot()["series"]["depth"]
    assert summ["max"] == telemetry.SERIES_CAP + 9
    assert summ["p50"] in vals                   # nearest-rank: a real sample
    assert t.series("absent") == ()


def test_percentiles_nearest_rank():
    assert telemetry.percentiles([]) == {}
    p = telemetry.percentiles([3.0, 1.0, 2.0, 4.0], qs=(50, 99))
    assert p == {"p50": 2.0, "p99": 4.0}         # ceil-rank order statistics
    assert telemetry.percentiles([7.0])["p50"] == 7.0


def test_global_sugar_routes_to_one_registry():
    before = telemetry.get_telemetry().get("test.sugar")
    telemetry.counter("test.sugar", 2)
    assert telemetry.get_telemetry().get("test.sugar") == before + 2
    assert telemetry.snapshot()["counters"]["test.sugar"] == before + 2


# ---------------------------------------------------------------------------
# measure(): the one timing harness
# ---------------------------------------------------------------------------


def test_measure_call_count_exact():
    # The old kernel_bench warmup called fn TWICE to probe its return type
    # (`fn(*args)[0] ... if isinstance(fn(*args), tuple)`); measure() must
    # call exactly warmup + iters times, whatever fn returns.
    calls = []
    m = measure(lambda: calls.append(1), iters=3, warmup=1)
    assert len(calls) == 4
    assert m.iters == 3
    assert all(t >= 0 for t in m.times_s)


def test_measure_handles_tuple_and_array_returns():
    x = jnp.arange(8.0)
    m_tuple = measure(lambda: (x * 2, x + 1), iters=2)
    m_array = measure(lambda: x * 2, iters=2)
    assert m_tuple.iters == m_array.iters == 2


def test_measure_statistics_and_validation():
    m = Measurement(name="n", times_s=(3e-3, 1e-3, 2e-3))
    assert m.best_s == 1e-3
    assert m.mean_s == pytest.approx(2e-3)
    assert m.best_us == pytest.approx(1e3)
    with pytest.raises(ValueError):
        measure(lambda: None, iters=0)


def test_measure_records_named_span():
    t = telemetry.get_telemetry()
    before = t.span_stat("measure.tm_probe")
    n0 = before.count if before else 0
    measure(lambda: None, iters=1, name="tm_probe")
    assert t.span_stat("measure.tm_probe").count == n0 + 1


def test_counter_ticks_at_trace_time_under_jit():
    # Counters are host-side Python state: inside a jitted function they
    # tick once per COMPILATION, not per call — the documented semantic
    # the kernel hooks rely on (plans/dispatches are trace-time work).
    t = Telemetry()

    @jax.jit
    def f(v):
        t.count("traced")
        return v * 2

    f(jnp.float32(1.0))
    f(jnp.float32(2.0))
    f(jnp.float32(3.0))
    assert t.get("traced") == 1
    f(jnp.arange(4.0))                     # new shape -> new trace
    assert t.get("traced") == 2


def test_staging_plan_hooks_count_issues_and_words():
    from repro.kernels.staging import strip_plan

    t = telemetry.get_telemetry()
    base = {k: t.get(k) for k in ("staging.plans", "staging.dma_issues",
                                  "staging.window_words")}
    plan = strip_plan(h_tot=18, w_tot=16, c_block=8, tile_h=4,
                      grid=(1, 4, 2), window_dims=(0, 1, 2), stride=1,
                      k_h=3, residency="strip_dma_db")
    assert t.get("staging.plans") == base["staging.plans"] + 1
    assert t.get("staging.dma_issues") == base["staging.dma_issues"] + 8
    assert t.get("staging.window_words") == (
        base["staging.window_words"] + 8 * plan.in_rows * 16 * 8)
    # resident plans issue no DMA
    strip_plan(h_tot=18, w_tot=16, c_block=8, tile_h=4,
               grid=(1, 4, 2), window_dims=(0, 1, 2), stride=1, k_h=3,
               residency="resident")
    assert t.get("staging.dma_issues") == base["staging.dma_issues"] + 8


# ---------------------------------------------------------------------------
# host fingerprint + BENCH round-trip
# ---------------------------------------------------------------------------


def test_host_fingerprint_and_slug():
    fp = telemetry.host_fingerprint()
    for key in ("node", "system", "machine", "python", "jax", "backend"):
        assert fp[key]
    slug = telemetry.host_slug({"node": "my host!", "backend": "cpu"})
    assert slug == "my-host-cpu"
    assert bench_filename({"node": "a", "backend": "cpu"}) == \
        "BENCH_a-cpu.json"


def _records(bytes0=1000, axes0=None, wall0=50.0):
    return [
        {"name": "l0", "shape": {"hw": 7},
         "axes": axes0 or {"tile_h": 4, "mode": "retain"},
         "modeled_bytes": bytes0, "walltime_us": wall0,
         "candidates": [
             {"axes": {"tile_h": 4, "mode": "retain"},
              "modeled_bytes": bytes0, "walltime_us": wall0},
             {"axes": {"tile_h": 4, "mode": "recompute"},
              "modeled_bytes": bytes0 + 500, "walltime_us": wall0 + 10},
         ]},
        {"name": "l1", "shape": {"hw": 14},
         "axes": {"tile_h": 8, "mode": "recompute"},
         "modeled_bytes": 2000, "walltime_us": 80.0},
    ]


def test_bench_round_trip(tmp_path):
    fp = {"node": "ci", "backend": "cpu", "machine": "x86_64",
          "system": "Linux", "jax": "0.4.37"}
    path = write_bench(tmp_path, _records(), config={"scale": 4},
                       counters={"counters": {"c": 1}, "spans": {}},
                       fingerprint=fp)
    assert path.name == "BENCH_ci-cpu.json"
    loaded = load_bench(path)
    assert [r["name"] for r in loaded["records"]] == ["l0", "l1"]
    assert loaded["config"]["scale"] == 4
    assert loaded["host"]["node"] == "ci"
    assert loaded["counters"]["counters"]["c"] == 1


def test_bench_schema_rejects_malformed():
    with pytest.raises(ValueError):
        validate_bench({"version": 1, "kind": "wrong", "records": [{}],
                        "host": {}})
    with pytest.raises(ValueError):
        validate_bench({"version": 1, "kind": "convdk-bench-trajectory",
                        "records": [], "host": {}})
    with pytest.raises(ValueError):                       # missing keys
        validate_bench({"version": 1, "kind": "convdk-bench-trajectory",
                        "records": [{"name": "x"}], "host": {}})
    with pytest.raises(ValueError):                       # duplicate name
        validate_bench({
            "version": 1, "kind": "convdk-bench-trajectory", "host": {},
            "records": _records() + _records()})


# ---------------------------------------------------------------------------
# the trajectory differ
# ---------------------------------------------------------------------------


def _bench(records, node="ci", config=None):
    return {"version": 1, "kind": "convdk-bench-trajectory",
            "host": {"node": node, "backend": "cpu", "machine": "x86_64",
                     "system": "Linux", "jax": "0.4.37"},
            "config": config or {"scale": 4}, "records": records}


def test_diff_clean_is_ok():
    d = diff_bench(_bench(_records()), _bench(_records()))
    assert d.ok and d.walltime_enforced


def test_diff_detects_modeled_bytes_regression():
    d = diff_bench(_bench(_records()), _bench(_records(bytes0=1500)))
    assert not d.ok
    assert any("modeled bytes regressed" in f for f in d.failures)


def test_diff_detects_axis_flip_and_allows_when_asked():
    new = _bench(_records(axes0={"tile_h": 2, "mode": "recompute"}))
    d = diff_bench(_bench(_records()), new)
    assert any("axes changed" in f for f in d.failures)
    d2 = diff_bench(_bench(_records()), new, allow_axis_changes=True)
    assert d2.ok


def test_diff_detects_missing_record():
    new = _bench(_records()[:1])
    d = diff_bench(_bench(_records()), new)
    assert any("disappeared" in f for f in d.failures)


def test_diff_walltime_gates_only_on_comparable_hosts():
    slow = _bench(_records(wall0=500.0))
    same_host = diff_bench(_bench(_records()), slow)
    assert not same_host.ok
    other_host = diff_bench(_bench(_records()),
                            _bench(_records(wall0=500.0), node="laptop"))
    assert other_host.ok                   # noted, not gated
    assert any("walltime" in n for n in other_host.notes)
    forced = diff_bench(_bench(_records()),
                        _bench(_records(wall0=500.0), node="laptop"),
                        enforce_walltime=True)
    assert not forced.ok


def test_diff_rejects_incomparable_config():
    d = diff_bench(_bench(_records()),
                   _bench(_records(), config={"scale": 8}))
    assert not d.ok
    assert any("config.scale" in f for f in d.failures)


def test_diff_cli_exit_codes(tmp_path, capsys):
    from repro.core.trajectory import main as traj_main

    fp = {"node": "ci", "backend": "cpu"}
    old = write_bench(tmp_path / "old.json", _records(), fingerprint=fp,
                      config={"scale": 4})
    new = write_bench(tmp_path / "new.json", _records(bytes0=9000),
                      fingerprint=fp, config={"scale": 4})
    assert traj_main(["diff", str(old), str(old)]) == 0
    assert traj_main(["diff", str(old), str(new)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "modeled bytes regressed" in out


def test_rank_agreement_controlled_pairs():
    recs = _records()
    agr = rank_agreement(recs, "mode")
    # one controlled pair: bytes0 < bytes0+500 and wall0 < wall0+10 agree
    assert agr == {"axis": "mode", "pairs": 1, "agree": 1,
                   "model_ties": 0, "agreement": 1.0}
    assert rank_agreement(recs, "residency") is None


# ---------------------------------------------------------------------------
# measured calibration fit
# ---------------------------------------------------------------------------


def test_fit_recovers_planted_coefficients():
    base, per_mb, per_issue = 7.0, 3.0, 0.25
    samples = [
        {"walltime_us": base + per_mb * mb + per_issue * di,
         "modeled_bytes": mb * 1e6, "dma_issues": di}
        for mb, di in [(1, 0), (2, 8), (4, 2), (8, 32), (3, 16)]]
    c = fit_perf_coefficients(samples)
    assert isinstance(c, PerfCoefficients)
    assert c.base_us == pytest.approx(base, abs=1e-6)
    assert c.us_per_mb == pytest.approx(per_mb, abs=1e-6)
    assert c.us_per_dma_issue == pytest.approx(per_issue, abs=1e-6)
    assert c.us_per_collective_mb == 0.0   # constant column -> dropped
    assert c.rms_us == pytest.approx(0.0, abs=1e-6)
    assert predict_walltime_us(
        c, modeled_bytes=2e6, dma_issues=8) == pytest.approx(
        base + 2 * per_mb + 8 * per_issue, abs=1e-6)


def test_fit_rejects_underdetermined():
    with pytest.raises(ValueError):
        fit_perf_coefficients([])
    with pytest.raises(ValueError):
        # 2 samples, 3 varying cost columns + intercept = 4 free terms
        fit_perf_coefficients([
            {"walltime_us": 1.0, "modeled_bytes": 1e6, "dma_issues": 1,
             "collective_bytes": 1e5},
            {"walltime_us": 2.0, "modeled_bytes": 2e6, "dma_issues": 3,
             "collective_bytes": 4e5}])
    # a single sample IS enough for an intercept-only fit (every cost
    # column constant -> dropped): degrade, don't crash
    c = fit_perf_coefficients(
        [{"walltime_us": 5.0, "modeled_bytes": 1e6}])
    assert c.base_us == pytest.approx(5.0)
    assert c.us_per_mb == 0.0
