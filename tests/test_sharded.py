"""Multi-device sharded executor (PR 8): bit-identity with the
single-device engine, model/live halo-transfer parity, per-shard
checkpoints with a consistent global cut, incremental snapshots, and
silent-shard heartbeat detection.

The live tests run on however many JAX devices the process has — on a
plain CPU host every shard shares one device (same graphs, same
transfers, same bits); the CI multi-device job re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` where the
placement assertions additionally engage.
"""

import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core.executor import AsyncExecutor
from repro.core.outofcore import OOCConfig, paper_code_fields
from repro.core.pipeline import (
    V100_PCIE,
    sharded_timeline,
    sweep_timeline,
)
from repro.core.sharded import ShardedExecutor
from repro.core.taskgraph import build_sharded_tasks
from repro.distributed.fault import HeartbeatMonitor

SHAPE = (96, 12, 10)
NDIV = 4


def _initial(shape, seed=0):
    rng = np.random.default_rng(seed)
    p_prev = rng.standard_normal(shape, dtype=np.float32)
    p_cur = rng.standard_normal(shape, dtype=np.float32)
    vel2 = (1.0 + rng.random(shape, dtype=np.float32)) * 0.05
    return p_prev, p_cur, vel2


def _cfg(bt=2, code=1):
    return OOCConfig(SHAPE, NDIV, bt, paper_code_fields(code))


def _devices(n):
    devs = jax.devices()
    return devs[:n] if len(devs) >= n else None


# ----------------------------------------------------------------------
# bit-identity with the single-device engine
# ----------------------------------------------------------------------
@pytest.mark.parametrize("schedule,bt", [
    ("unitgrain", 2), ("depth2", 2), ("temporal2", 1),
])
@pytest.mark.parametrize("budget", [0, 1 << 30])
def test_bit_identical_to_single_device(schedule, bt, budget):
    """Every schedule x residency budget: the 2-shard run commits
    exactly the bytes the single-device engine does — the ghost fetch
    decodes the unit the neighbor committed, the held import is the
    on-device carry, and op order is unchanged."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    sweeps = 3
    ref = AsyncExecutor(
        _cfg(bt), p_prev, p_cur, vel2,
        schedule=schedule, cache_bytes=budget,
    )
    ref.run(sweeps * bt)
    sh = ShardedExecutor(
        _cfg(bt), p_prev, p_cur, vel2, nshards=2,
        schedule=schedule, cache_bytes=budget, devices=_devices(2),
    )
    sh.run_sweeps(sweeps)
    for name in ("p_prev", "p_cur"):
        assert np.array_equal(ref.gather(name), sh.gather(name)), name


def test_four_shards_one_block_each():
    """The degenerate tiling (nblocks == nshards) still reproduces the
    single-device bits — every boundary is an inter-shard boundary."""
    p_prev, p_cur, vel2 = _initial(SHAPE, seed=3)
    ref = AsyncExecutor(_cfg(), p_prev, p_cur, vel2, schedule="depth2")
    ref.run(3 * 2)
    sh = ShardedExecutor(
        _cfg(), p_prev, p_cur, vel2, nshards=4,
        schedule="depth2", devices=_devices(4),
    )
    sh.run_sweeps(3)
    assert np.array_equal(ref.gather("p_cur"), sh.gather("p_cur"))
    assert np.array_equal(ref.gather("p_prev"), sh.gather("p_prev"))


@pytest.mark.parametrize("budget", [0, 1 << 30])
def test_halo_puts_collect_copies_started_at_park(budget, host_copies):
    """Every put of the rounds, unit halos included, collects a host
    copy started when its visit was parked: without residency the
    halo's copy is its writeback's own; with the writebacks committed
    on the device, the halo starts one. No array is started twice, and
    the bits are one device's."""
    p_prev, p_cur, vel2 = _initial(SHAPE, seed=4)
    ref = AsyncExecutor(_cfg(code=4), p_prev, p_cur, vel2,
                        schedule="depth2", cache_bytes=budget)
    ref.run(3 * 2)
    sh = ShardedExecutor(
        _cfg(code=4), p_prev, p_cur, vel2, nshards=4,
        schedule="depth2", cache_bytes=budget, devices=_devices(4),
    )
    started, puts = host_copies
    started.clear(), puts.clear()
    sh.run_sweeps(3)
    sh.finish()
    halo = [m for m in puts if m["op"] == "halo"]
    assert halo and all(m["early"] for m in puts)
    assert len(halo) == 3 * (4 - 1) * 2  # rounds x boundaries x rw fields
    if budget:  # the drains commit on the device: only halos start
        assert len(started) == len(puts) == len(halo)
    assert len({id(v) for v in started}) == len(started)
    assert sum(st["wire"]["d2h_early"]
               for st in sh.stats()["per_device"].values()) == len(puts)
    for name in ("p_prev", "p_cur"):
        assert np.array_equal(ref.gather(name), sh.gather(name)), name


@pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 JAX devices"
)
def test_shards_pinned_to_distinct_devices():
    p_prev, p_cur, vel2 = _initial(SHAPE)
    sh = ShardedExecutor(
        _cfg(), p_prev, p_cur, vel2, nshards=2,
        schedule="depth2", devices=jax.devices()[:2],
    )
    assert [s.device for s in sh.specs] == jax.devices()[:2]
    sh.run_sweeps(2)
    out = sh.gather("p_cur")
    assert out.shape == SHAPE


# ----------------------------------------------------------------------
# model/live transfer parity, halos included
# ----------------------------------------------------------------------
@pytest.mark.parametrize("budget", [0, 1 << 30])
def test_model_live_transfer_multiset_parity(budget):
    """The merged sharded graph prices exactly the transfers the live
    coordinator pays — h2d, d2h, flush, and BOTH halo flows — as a
    multiset of (kind, field, unit, sweep, flush) at every budget."""
    p_prev, p_cur, vel2 = _initial(SHAPE, seed=2)
    cfg = _cfg()
    sh = ShardedExecutor(
        cfg, p_prev, p_cur, vel2, nshards=2, schedule="depth2",
        cache_bytes=budget, devices=_devices(2),
    )
    sh.run_sweeps(3)
    sh.finish()
    stats = {}
    tasks = build_sharded_tasks(
        cfg, 2, sweeps=3, schedule="depth2", cache_bytes=budget,
        stats=stats,
    )
    graph = sorted(
        (t.kind, t.field, t.unit, t.sweep, t.flush)
        for t in tasks if t.kind in ("h2d", "d2h", "halo")
    )
    issued = sorted(
        (t.direction, t.field, t.unit, t.sweep, t.flush)
        for t in sh.transfers
    )
    assert issued == graph
    # halo wire bytes agree exactly: the graph prices the encoded
    # payload at the live Compressed size (raw held slices verbatim)
    modeled_halo = sum(t.amount for t in tasks if t.kind == "halo")
    real = sh.transfer_summary()
    assert real["halo_wire"] == modeled_halo
    # per-shard modeled residency counters were populated
    assert set(stats["per_device"]) == {0, 1}


def test_transfer_summary_per_device_breakdown():
    p_prev, p_cur, vel2 = _initial(SHAPE)
    sh = ShardedExecutor(
        _cfg(), p_prev, p_cur, vel2, nshards=2,
        schedule="depth2", devices=_devices(2),
    )
    sh.run_sweeps(2)
    ts = sh.transfer_summary()
    assert ts["halo_count"] == sum(
        v["halo_count"] for v in ts["per_device"].values()
    )
    assert ts["halo_wire"] == sum(
        v["halo_wire"] for v in ts["per_device"].values()
    )
    # CacheStats mirrors the per-device halo accounting (satellite:
    # per-device/per-kind breakdowns in the stats surfaces)
    for d, ex in enumerate(sh.shards):
        cs = ex.cache.stats.as_dict()
        assert cs["halo_count"] == ts["per_device"][d]["halo_count"]
        assert cs["halo_wire_bytes"] == ts["per_device"][d]["halo_wire"]


def test_modeled_sharded_speedup():
    """DES: per-sweep makespan drops toward 1/N — the 4-shard replay
    of the smoke-bench geometry finishes in <= 0.5x the 1-shard
    makespan (the bench-guarded invariant)."""
    cfg = OOCConfig((192, 16, 16), 8, 2, paper_code_fields(1))
    base = sweep_timeline(cfg, V100_PCIE, sweeps=4, schedule="depth2")
    tl = sharded_timeline(cfg, V100_PCIE, 4, sweeps=4, schedule="depth2")
    assert tl.makespan <= 0.5 * base.makespan
    assert tl.transfer_wire()["halo_wire"] > 0
    # shards own namespaced streams; halo links appear in occupancy
    res = tl.busy_by_resource()
    assert any(r.startswith("s0:") for r in res)
    assert any(r.endswith(":halo") for r in res)


# ----------------------------------------------------------------------
# per-shard checkpoints, consistent cut, incremental snapshots
# ----------------------------------------------------------------------
def test_sharded_checkpoint_restore_bit_identical(tmp_path):
    p_prev, p_cur, vel2 = _initial(SHAPE, seed=1)
    sh = ShardedExecutor(
        _cfg(), p_prev, p_cur, vel2, nshards=2,
        schedule="depth2", devices=_devices(2),
    )
    d = str(tmp_path)
    sh.run_sweeps(2)
    sh.checkpoint(d)
    sh.run_sweeps(1)
    sh.checkpoint(d, incremental=True)
    sh.run_sweeps(1)
    want = {n: sh.gather(n) for n in ("p_prev", "p_cur")}
    rest = ShardedExecutor.restore(d, devices=_devices(2))
    assert rest.sweeps_done == 3
    rest.run_sweeps(1)
    for n, arr in want.items():
        assert np.array_equal(arr, rest.gather(n)), n


def test_incremental_checkpoint_reuses_unchanged_units(tmp_path):
    """The differential cut: units whose version did not move point at
    the previous checkpoint's shard files (external ``dir`` entries,
    chains flattened to the original writer); restore reads through
    the references bit-identically."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    ex = AsyncExecutor(_cfg(), p_prev, p_cur, vel2, schedule="depth2")
    d = str(tmp_path)
    ex.run(2)
    ex.checkpoint(d)
    ex.run(2)
    ex.checkpoint(d, incremental=True)
    ex.run(2)
    ex.checkpoint(d, incremental=True)
    steps = sorted(
        p for p in pathlib.Path(d).iterdir()
        if p.name.startswith("step_")
    )
    assert len(steps) == 3
    first = steps[0].name
    m2 = json.loads((steps[1] / "manifest.json").read_text())
    m3 = json.loads((steps[2] / "manifest.json").read_text())
    ext2 = {k: e for k, e in m2["leaves"].items() if "dir" in e}
    ext3 = {k: e for k, e in m3["leaves"].items() if "dir" in e}
    # the read-only velocity units never move -> reused in every cut
    assert ext2 and ext3
    assert all(e["dir"] == first for e in ext2.values())
    # chain-flattening: the third cut references the ORIGINAL writer,
    # not the second cut
    assert all(e["dir"] == first for e in ext3.values())
    assert ex.ckpt_stats["units_reused"] == len(ext2) + len(ext3)
    # restore decodes through the external references
    rest = AsyncExecutor.restore(d)
    ex2 = AsyncExecutor(_cfg(), p_prev, p_cur, vel2, schedule="depth2")
    ex2.run(6)
    assert np.array_equal(rest.gather("p_cur"), ex2.gather("p_cur"))


def test_incremental_gc_keeps_referenced_sources(tmp_path):
    """keep=1 must NOT collect a checkpoint an incremental chain still
    points into — and a later full snapshot releases it."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    ex = AsyncExecutor(_cfg(), p_prev, p_cur, vel2, schedule="depth2")
    d = str(tmp_path)
    ex.run(2)
    ex.checkpoint(d, keep=1)
    ex.run(2)
    ex.checkpoint(d, keep=1, incremental=True)
    names = sorted(
        p.name for p in pathlib.Path(d).iterdir()
        if p.name.startswith("step_")
    )
    assert len(names) == 2  # source pinned by the reference
    rest = AsyncExecutor.restore(d)
    assert np.array_equal(rest.gather("p_cur"), ex.gather("p_cur"))
    ex.run(2)
    ex.checkpoint(d, keep=1)  # full cut: chain broken
    names = sorted(
        p.name for p in pathlib.Path(d).iterdir()
        if p.name.startswith("step_")
    )
    assert len(names) == 1


# ----------------------------------------------------------------------
# heartbeat: silent/slow shard detection in the coordinator
# ----------------------------------------------------------------------
def test_straggler_shard_surfaces_in_recovery_stats():
    p_prev, p_cur, vel2 = _initial(SHAPE)
    sh = ShardedExecutor(
        _cfg(), p_prev, p_cur, vel2, nshards=2, schedule="depth2",
        devices=_devices(2),
        monitor=HeartbeatMonitor(2, straggler_factor=1.2),
    )
    # scripted clock: per round the coordinator reads beat(shard0),
    # beat(shard1), then the straggler check — shard 1's cadence is 5x
    # shard 0's, well past 1.2x the fleet median of (1+5)/2
    ticks = []
    for r in range(4):
        ticks += [r * 1.0, r * 5.0, r * 5.0 + 0.1]
    it = iter(ticks)
    last = ticks[-1]
    sh._timer = lambda: next(it, last)
    sh.run_sweeps(4)
    st = sh.stats()
    assert st["heartbeat"]["straggler_rounds"] >= 1
    rows = [r for r in sh.recovery_log if r["kind"] == "straggler"]
    assert rows and all(1 in r["shards"] for r in rows)
    assert st["heartbeat"]["median_round_time_s"] is not None


def test_mid_cut_guard(tmp_path):
    """A checkpoint with shards at different sweep cursors is refused
    — the global cut must be consistent."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    sh = ShardedExecutor(
        _cfg(), p_prev, p_cur, vel2, nshards=2, schedule="depth2",
        devices=_devices(2),
    )
    sh.run_sweeps(1)
    sh.shards[0].sweep(1)  # desync one shard behind the API's back
    with pytest.raises(AssertionError):
        sh.checkpoint(str(tmp_path))
