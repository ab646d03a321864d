"""Persistent (JSON) schedule cache: disk round-trips, measured-entry
priority, and graceful degradation without a cache dir."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autotune import (
    ScheduleCache,
    TPUConfig,
    benchmark_fused_sweep,
    get_fused_schedule,
    get_mbconv_schedule,
    get_schedule_cache,
    set_schedule_cache_dir,
)


@pytest.fixture
def cache_dir(tmp_path):
    """Point the global schedule cache at a temp dir; restore afterwards."""
    cache = set_schedule_cache_dir(tmp_path)
    yield tmp_path, cache
    set_schedule_cache_dir(None)


def _entries(tmp_path):
    payload = json.loads((tmp_path / "convdk_schedules.json").read_text())
    assert payload["version"] == 1
    return payload["entries"]


def test_schedule_persists_to_disk(cache_dir):
    tmp_path, cache = cache_dir
    sch = get_fused_schedule(1, 56, 56, 144, 24, 3, 1)
    entries = _entries(tmp_path)
    (key,) = [k for k in entries if k.startswith("sep|")]
    assert "b1-h56-w56-ci144-co24-k3-s1" in key
    assert entries[key]["tile_h"] == sch.tile_h
    assert entries[key]["source"] == "model"

    msch = get_mbconv_schedule(1, 14, 14, 80, 480, 112, 5, 1)
    entries = _entries(tmp_path)
    (mkey,) = [k for k in entries if k.startswith("mbconv|")]
    assert "ci80-cm480-co112-k5-s1" in mkey
    assert entries[mkey]["mode"] == msch.mode


def test_disk_entry_survives_process_restart(cache_dir):
    """A restart is simulated by dropping the in-process layer: the lookup
    must come back from the JSON file (proved by editing the file)."""
    tmp_path, cache = cache_dir
    get_fused_schedule(1, 28, 28, 192, 64, 3, 2)
    entries = _entries(tmp_path)
    (key,) = list(entries)
    edited = dict(entries[key], tile_h=2, source="measured")
    (tmp_path / "convdk_schedules.json").write_text(
        json.dumps({"version": 1, "entries": {key: edited}}))

    cache.clear_memory()                       # "new process"
    sch = get_fused_schedule(1, 28, 28, 192, 64, 3, 2)
    assert sch.tile_h == 2                     # came from disk, not the model


def test_measured_sweep_persists_and_outranks_model(cache_dir):
    tmp_path, cache = cache_dir
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 12, 12, 8)), jnp.float32)
    w_dw = jnp.asarray(rng.normal(size=(3, 3, 8)), jnp.float32)
    w_pw = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    best, results = benchmark_fused_sweep(
        x, w_dw, w_pw, stride=1, tile_hs=[1, 4], iters=1, interpret=True,
        persist=True)
    assert dict(results).keys() == {1, 4}
    entries = _entries(tmp_path)
    (key,) = [k for k in entries if "ci8-co16" in k]
    assert entries[key]["source"] == "measured"
    assert entries[key]["tile_h"] == best

    # a later model pick must NOT clobber the measured ground truth...
    cache.clear_memory()
    sch = get_fused_schedule(1, 12, 12, 8, 16, 3, 1)
    assert sch.tile_h == best
    assert _entries(tmp_path)[key]["source"] == "measured"


def test_invalid_disk_tile_h_falls_back_to_model(cache_dir):
    tmp_path, cache = cache_dir
    get_fused_schedule(1, 16, 16, 8, 8, 3, 1)
    entries = _entries(tmp_path)
    (key,) = list(entries)
    entries[key]["tile_h"] = 9999              # > out_h: stale / corrupt
    (tmp_path / "convdk_schedules.json").write_text(
        json.dumps({"version": 1, "entries": entries}))
    cache.clear_memory()
    sch = get_fused_schedule(1, 16, 16, 8, 8, 3, 1)
    assert 1 <= sch.tile_h <= 16


def test_malformed_entry_falls_back_to_model(cache_dir):
    """A valid-JSON file with a garbage ENTRY (wrong type, missing or
    non-numeric tile_h, bad mode) must degrade to the analytical model,
    never crash schedule lookup."""
    tmp_path, cache = cache_dir
    want = get_fused_schedule(1, 16, 16, 8, 8, 3, 1)
    mwant = get_mbconv_schedule(1, 14, 14, 16, 64, 24, 3, 1)
    entries = _entries(tmp_path)
    (skey,) = [k for k in entries if k.startswith("sep|")]
    (mkey,) = [k for k in entries if k.startswith("mbconv|")]
    for bad_sep, bad_mb in [
        ("garbage", "garbage"),                      # non-dict entry
        ({}, {}),                                    # missing tile_h
        ({"tile_h": "huge"}, {"tile_h": None}),      # non-numeric tile_h
        ({"tile_h": [4]}, {"tile_h": 4, "mode": "teleport"}),  # bad mode
    ]:
        (tmp_path / "convdk_schedules.json").write_text(json.dumps(
            {"version": 1, "entries": {skey: bad_sep, mkey: bad_mb}}))
        cache.clear_memory()
        assert get_fused_schedule(1, 16, 16, 8, 8, 3, 1) == want
        assert get_mbconv_schedule(1, 14, 14, 16, 64, 24, 3, 1) == mwant


def test_cache_key_includes_full_tpu_config(cache_dir):
    """Schedules solved under one TPUConfig are never reused for another:
    c_block and the tile_h candidate set are part of the key."""
    tmp_path, _cache = cache_dir
    base = TPUConfig()
    get_fused_schedule(1, 56, 56, 144, 24, 3, 1, tpu=base)
    wide = TPUConfig(c_block=256)
    sch = get_fused_schedule(1, 56, 56, 144, 24, 3, 1, tpu=wide)
    assert sch.ci_block == 256                   # solved, not cache-echoed
    coarse = TPUConfig(tile_h_candidates=(2,))
    sch2 = get_fused_schedule(1, 56, 56, 144, 24, 3, 1, tpu=coarse)
    assert sch2.tile_h == 2
    assert len(_entries(tmp_path)) == 3          # three distinct keys


def test_sharded_and_unsharded_picks_do_not_collide(cache_dir):
    """Regression for the mesh_shape cache axis: sharded and unsharded
    schedules for the SAME layer shape live under distinct keys, and a
    disk round-trip edits exactly the partitioning it targets."""
    tmp_path, cache = cache_dir
    base = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1)
    sharded = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                                  mesh_shape=(2, 4))
    assert base.mesh_shape == (1, 1) and sharded.mesh_shape == (2, 4)
    entries = _entries(tmp_path)
    keys = [k for k in entries if k.startswith("mbconv|")]
    assert len(keys) == 2                      # no collision
    (ukey,) = [k for k in keys if "|mesh1x1|" in k]
    (skey,) = [k for k in keys if "|mesh2x4|" in k]
    assert ukey.replace("|mesh1x1|", "|mesh2x4|") == skey   # same layer key

    # round-trip: a measured edit to the SHARDED entry survives a
    # "restart" and steers only the sharded lookup
    entries[skey] = dict(entries[skey], tile_h=1, mode="recompute",
                         source="measured")
    (tmp_path / "convdk_schedules.json").write_text(
        json.dumps({"version": 1, "entries": entries}))
    cache.clear_memory()
    again = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                                mesh_shape=(2, 4))
    assert (again.tile_h, again.mode) == (1, "recompute")
    unsharded = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1)
    assert (unsharded.tile_h, unsharded.mode) == (base.tile_h, base.mode)

    # separable family too, and non-divisible grids normalize to the
    # EFFECTIVE factors — all-or-nothing, exactly the kernel routing's
    # can_shard_fused policy, so the cache never holds a partitioning the
    # kernels will not run
    get_fused_schedule(8, 28, 28, 64, 64, 3, 1)
    sharded_sep = get_fused_schedule(8, 28, 28, 64, 64, 3, 1,
                                     mesh_shape=(4, 2))
    assert sharded_sep.mesh_shape == (4, 2)
    half = get_fused_schedule(8, 28, 28, 64, 63, 3, 1, mesh_shape=(4, 2))
    assert half.mesh_shape == (1, 1)           # batch divides, c_out no ->
    odd = get_fused_schedule(7, 28, 28, 64, 63, 3, 1, mesh_shape=(4, 2))
    assert odd.mesh_shape == (1, 1)            # ... whole layer 1-core
    sep_keys = [k for k in _entries(tmp_path) if k.startswith("sep|")]
    assert sorted(k.split("|")[3] for k in sep_keys) == \
        ["mesh1x1", "mesh1x1", "mesh1x1", "mesh4x2"]


def test_legacy_pre_mesh_keys_migrate(cache_dir):
    """Entries persisted before the mesh_shape key axis (no ``mesh``
    segment) were all single-device picks: they must be honored as the
    ``mesh1x1`` entries — a measured sweep from an old deployment keeps
    outranking model picks instead of being silently orphaned."""
    tmp_path, cache = cache_dir
    sch = get_fused_schedule(1, 28, 28, 192, 64, 3, 2)
    (key,) = list(_entries(tmp_path))
    # the pre-mesh era predates BOTH later key axes (mesh and residency)
    legacy_key = key.replace("|mesh1x1|", "|").replace("|res=auto|", "|")
    assert "|mesh" not in legacy_key and "|res=" not in legacy_key \
        and len(legacy_key.split("|")) == 5
    edited = 2 if sch.tile_h != 2 else 4
    (tmp_path / "convdk_schedules.json").write_text(json.dumps(
        {"version": 1,
         "entries": {legacy_key: {"tile_h": edited, "source": "measured"}}}))
    cache.clear_memory()                       # "new process", old file
    assert get_fused_schedule(1, 28, 28, 192, 64, 3, 2).tile_h == edited


def test_legacy_pre_collective_keys_migrate(cache_dir):
    """MBConv entries persisted before the collective axis (no ``coll=``
    segment) must be honored as the ``coll=auto`` picks — the measured
    (tile_h, mode) wins and the collective is re-solved at that point —
    while separable keys never grow the segment."""
    tmp_path, cache = cache_dir
    sch = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                              mesh_shape=(2, 4))
    assert sch.collective in ("ring_allreduce", "psum_scatter")
    (key,) = list(_entries(tmp_path))
    assert "|coll=auto|" in key
    legacy_key = key.replace("|coll=auto|", "|")       # pre-collective era
    edited_th = 1 if sch.tile_h != 1 else 2
    (tmp_path / "convdk_schedules.json").write_text(json.dumps(
        {"version": 1,
         "entries": {legacy_key: {"tile_h": edited_th, "mode": "recompute",
                                  "source": "measured"}}}))
    cache.clear_memory()                               # "new process"
    again = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                                mesh_shape=(2, 4))
    assert (again.tile_h, again.mode) == (edited_th, "recompute")
    assert again.collective in ("ring_allreduce", "psum_scatter")

    # a pinned collective solves (and caches) under its own key
    ring = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                               mesh_shape=(2, 4),
                               collective="ring_allreduce")
    assert ring.collective == "ring_allreduce"
    assert any("|coll=ring_allreduce|" in k for k in _entries(tmp_path))

    get_fused_schedule(8, 28, 28, 64, 64, 3, 1, mesh_shape=(2, 4))
    sep_keys = [k for k in _entries(tmp_path) if k.startswith("sep|")]
    assert sep_keys and all("coll=" not in k for k in sep_keys)


def test_legacy_pre_layout_keys_migrate(cache_dir):
    """MBConv entries persisted before the input-layout axis (no
    ``layout=`` segment) were all solved for a replicated arrival — the
    only entry form that existed — so they must be honored as the
    ``layout=replicated`` picks after a disk round-trip, while a
    c_in-sharded arrival solves (and caches) under its own
    ``layout=model_sharded`` key instead of echoing the replicated
    schedule."""
    tmp_path, cache = cache_dir
    sch = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                              mesh_shape=(2, 4))
    (key,) = list(_entries(tmp_path))
    assert "|layout=replicated|" in key
    legacy_key = key.replace("|layout=replicated|", "|")   # pre-layout era
    assert "layout=" not in legacy_key
    edited_th = 1 if sch.tile_h != 1 else 2
    (tmp_path / "convdk_schedules.json").write_text(json.dumps(
        {"version": 1,
         "entries": {legacy_key: {"tile_h": edited_th, "mode": "recompute",
                                  "source": "measured"}}}))
    cache.clear_memory()                                   # "new process"
    again = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                                mesh_shape=(2, 4))
    assert (again.tile_h, again.mode) == (edited_th, "recompute")
    assert again.in_layout == "replicated"

    # a sharded arrival must NOT hit the migrated replicated entry: it
    # solves fresh and persists under layout=model_sharded
    sharded = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                                  mesh_shape=(2, 4),
                                  in_layout="model_sharded")
    assert sharded.in_layout == "model_sharded"
    keys = list(_entries(tmp_path))
    assert any("|layout=model_sharded|" in k for k in keys)
    assert any("|layout=replicated|" in k for k in keys)


def test_legacy_pre_overlap_keys_migrate(cache_dir):
    """MBConv entries persisted before the cross-block overlap axis (no
    ``ov=`` segment) were all solved under the serial-entry VMEM budget —
    so they must be honored as the ``ov=serial`` picks after a disk
    round-trip, while a pipelined entry (halved pass-1 VMEM budget)
    solves and caches under its own ``ov=pipelined`` key instead of
    echoing the serial schedule."""
    tmp_path, cache = cache_dir
    sch = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                              mesh_shape=(2, 4))
    (key,) = list(_entries(tmp_path))
    assert "|ov=serial|" in key
    legacy_key = key.replace("|ov=serial|", "|")           # pre-overlap era
    assert "ov=" not in legacy_key
    edited_th = 1 if sch.tile_h != 1 else 2
    (tmp_path / "convdk_schedules.json").write_text(json.dumps(
        {"version": 1,
         "entries": {legacy_key: {"tile_h": edited_th, "mode": "recompute",
                                  "source": "measured"}}}))
    cache.clear_memory()                                   # "new process"
    again = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                                mesh_shape=(2, 4))
    assert (again.tile_h, again.mode) == (edited_th, "recompute")
    assert again.overlap == "serial"

    # a pipelined entry must NOT hit the migrated serial entry: it
    # solves fresh (halved pass-1 budget) and persists under ov=pipelined
    pipe = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                               mesh_shape=(2, 4), overlap="pipelined")
    assert pipe.overlap == "pipelined"
    keys = list(_entries(tmp_path))
    assert any("|ov=pipelined|" in k for k in keys)
    assert any("|ov=serial|" in k for k in keys)

    # separable keys never grow the segment
    get_fused_schedule(8, 28, 28, 64, 64, 3, 1, mesh_shape=(2, 4))
    sep_keys = [k for k in _entries(tmp_path) if k.startswith("sep|")]
    assert sep_keys and all("ov=" not in k for k in sep_keys)


def test_legacy_pre_family_keys_migrate(cache_dir):
    """MBConv entries persisted before the family axes (no ``act=`` /
    ``se=`` segments) were all silu + SE-on picks — the only variant that
    existed — so they must be honored as the ``act=silu|se=on`` entries
    after a disk round-trip (no cold re-solve of a measured schedule),
    while se=off and hard_swish solves cache under their OWN keys instead
    of echoing the migrated pick."""
    tmp_path, cache = cache_dir
    sch = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                              mesh_shape=(2, 4))
    (key,) = list(_entries(tmp_path))
    assert "|act=silu|se=on|" in key
    legacy_key = key.replace("|act=silu|se=on|", "|")    # pre-family era
    assert "act=" not in legacy_key and "se=" not in legacy_key
    edited_th = 1 if sch.tile_h != 1 else 2
    (tmp_path / "convdk_schedules.json").write_text(json.dumps(
        {"version": 1,
         "entries": {legacy_key: {"tile_h": edited_th, "mode": "recompute",
                                  "source": "measured"}}}))
    cache.clear_memory()                                 # "new process"
    again = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                                mesh_shape=(2, 4))
    assert (again.tile_h, again.mode) == (edited_th, "recompute")

    # the se=off and hard_swish variants must NOT hit the migrated silu
    # se-on entry: they solve fresh and persist under their own segments
    no_se = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                                mesh_shape=(2, 4), se_ratio=0.0)
    hs = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1,
                             mesh_shape=(2, 4), act="hard_swish")
    assert no_se.traffic.total_bytes <= again.traffic.total_bytes
    keys = list(_entries(tmp_path))
    assert any("|act=silu|se=off|" in k for k in keys)
    assert any("|act=hard_swish|se=on|" in k for k in keys)
    assert hs.tile_h >= 1

    # the CHAIN end to end: a key from the original (pre-mesh, pre-res,
    # pre-coll, pre-layout, pre-overlap, pre-family) era walks all six
    # migrations and still lands on the modern entry
    oldest = key
    for seg in ("|mesh2x4|", "|res=auto|", "|coll=auto|",
                "|layout=replicated|", "|ov=serial|", "|act=silu|se=on|"):
        oldest = oldest.replace(seg, "|")
    assert len(oldest.split("|")) < len(key.split("|"))
    (tmp_path / "convdk_schedules.json").write_text(json.dumps(
        {"version": 1,
         "entries": {oldest: {"tile_h": edited_th, "mode": "recompute",
                              "source": "measured"}}}))
    cache.clear_memory()
    chained = get_mbconv_schedule(8, 14, 14, 80, 480, 112, 5, 1)
    assert (chained.tile_h, chained.mode) == (edited_th, "recompute")

    # separable keys never grow the family segments
    get_fused_schedule(8, 28, 28, 64, 64, 3, 1)
    sep_keys = [k for k in _entries(tmp_path) if k.startswith("sep|")]
    assert sep_keys and all("act=" not in k and "se=" not in k
                            for k in sep_keys)


def test_corrupt_cache_file_is_ignored(cache_dir):
    tmp_path, _cache = cache_dir
    (tmp_path / "convdk_schedules.json").write_text("{not json")
    sch = get_fused_schedule(1, 8, 8, 8, 8, 3, 1)
    assert sch.tile_h >= 1
    # and the file heals on the next write
    assert _entries(tmp_path)


def test_memory_only_mode_without_dir():
    set_schedule_cache_dir(None)
    try:
        cache = get_schedule_cache()
        assert cache.path is None
        a = get_fused_schedule(1, 20, 20, 16, 16, 3, 1)
        b = get_fused_schedule(1, 20, 20, 16, 16, 3, 1)
        assert a == b                          # in-process layer still works
    finally:
        set_schedule_cache_dir(None)


def test_cache_isolated_per_shape_and_kind(cache_dir):
    tmp_path, _cache = cache_dir
    get_fused_schedule(1, 14, 14, 48, 64, 5, 1)
    get_mbconv_schedule(1, 14, 14, 48, 192, 64, 5, 1)
    get_mbconv_schedule(1, 14, 14, 48, 192, 64, 5, 2)
    assert len(_entries(tmp_path)) == 3


def test_schedule_cache_ignores_unwritable_dir(tmp_path):
    """Persistence is best-effort: an unwritable dir must not break
    schedule selection."""
    cache = ScheduleCache(tmp_path / "missing" / "x")
    cache.directory = tmp_path / "convdk_schedules.json"  # a FILE, not a dir
    cache.directory.write_text("occupied")
    cache.put("k", {"tile_h": 1, "source": "model"})
    assert cache.get("k") == {"tile_h": 1, "source": "model"}


# ---------------------------------------------------------------------------
# telemetry counters (hit/miss/put/migration)
# ---------------------------------------------------------------------------


def _counts():
    from repro.core import telemetry
    t = telemetry.get_telemetry()
    return {k: t.get(f"schedule_cache.{k}")
            for k in ("hit.memory", "hit.disk", "miss", "put",
                      "migrated_keys")}


def test_cache_counters_hit_miss_put(cache_dir):
    tmp_path, cache = cache_dir
    base = _counts()
    get_fused_schedule(1, 30, 30, 64, 32, 3, 1)     # miss -> solve -> put
    after_solve = _counts()
    assert after_solve["miss"] == base["miss"] + 1
    assert after_solve["put"] == base["put"] + 1
    get_fused_schedule(1, 30, 30, 64, 32, 3, 1)     # in-process hit
    after_mem = _counts()
    assert after_mem["hit.memory"] == after_solve["hit.memory"] + 1
    assert after_mem["miss"] == after_solve["miss"]
    cache.clear_memory()                            # simulated restart
    get_fused_schedule(1, 30, 30, 64, 32, 3, 1)     # disk hit
    after_disk = _counts()
    assert after_disk["hit.disk"] == after_mem["hit.disk"] + 1
    assert after_disk["put"] == after_mem["put"]    # echo, no re-record


def test_cache_counters_migration(cache_dir):
    import json as _json

    tmp_path, cache = cache_dir
    legacy = "sep|b1-h30-w30-ci64-co32-k3-s1|dtb4|v16777216-c128-t1.2.4.8.16.32|cpu"
    (tmp_path / "convdk_schedules.json").write_text(_json.dumps(
        {"version": 1, "entries": {
            legacy: {"tile_h": 4, "source": "measured"}}}))
    cache.clear_memory()
    base = _counts()
    get_fused_schedule(1, 30, 30, 64, 32, 3, 1)
    after = _counts()
    assert after["migrated_keys"] == base["migrated_keys"] + 1
