"""Live async executor vs synchronous engine and simulator.

The executor's contract:
* bit-identical field output to ``OutOfCoreWave`` (same ops, same
  values, any overlap) across block-count/compression configurations;
* the in-flight window bound is respected (depth-k accounting);
* transfers are issued through the shared task graph — the live
  engine's transfer log matches the simulator's h2d/d2h task set.
"""

import numpy as np
import pytest

from repro.core.executor import AsyncExecutor
from repro.core.outofcore import OOCConfig, OutOfCoreWave, paper_code_fields
from repro.core.taskgraph import (
    build_sweep_tasks,
    depth_k,
    get_schedule,
    wire_totals,
)
from repro.kernels.stencil import ref as stencil_ref

SHAPE = (96, 12, 12)
BT = 2


def _initial(shape):
    p_cur = np.asarray(stencil_ref.ricker_source(shape), dtype=np.float32)
    p_prev = 0.95 * p_cur
    vel2 = np.full(shape, 0.07, dtype=np.float32)
    return p_prev, p_cur, vel2


def _pair(code, ndiv, schedule="depth2", sweeps=2):
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, ndiv, BT, paper_code_fields(code))
    sync = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, schedule=schedule)
    sync.run(sweeps * BT)
    live.run(sweeps * BT)
    return sync, live


@pytest.mark.parametrize("code,ndiv", [(1, 4), (2, 4), (4, 3)])
def test_bit_identical_to_sync_engine(code, ndiv):
    """≥2 block-count/compression configs, uncompressed AND compressed:
    the overlapped execution must not change a single bit."""
    sync, live = _pair(code, ndiv)
    for name in ("p_cur", "p_prev"):
        np.testing.assert_array_equal(
            live.gather(name), sync.gather(name)
        )


@pytest.mark.parametrize("schedule", ["paper", "unitgrain", "depth3"])
def test_schedules_do_not_change_numerics(schedule):
    sync, live = _pair(4, 4, schedule=schedule, sweeps=1)
    np.testing.assert_array_equal(
        live.gather("p_cur"), sync.gather("p_cur")
    )


def test_transfer_totals_match_sync_engine():
    """Same units crossing the link → identical byte accounting."""
    sync, live = _pair(2, 4)
    assert live.transfer_summary() == sync.transfer_summary()


@pytest.mark.parametrize("k", [1, 2, 3])
def test_inflight_window_depth_accounting(k):
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(1))
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, schedule=depth_k(k))
    live.run(2 * BT)
    stats = live.stats()
    assert stats["depth"] == k
    # peak residency reaches but never exceeds the window bound
    assert stats["max_inflight"] == min(k, cfg.ndiv)


def test_live_transfers_match_simulator_graph():
    """Schedule equivalence: every h2d/d2h task the simulator replays
    is issued exactly once by the live executor (same field, unit and
    block), and modeled wire bytes track the real payloads."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(2))
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, schedule="paper")
    live.sweep()
    live.finish()  # materialize the parked tail of the window
    tasks = build_sweep_tasks(cfg, sweeps=1, schedule="paper")
    graph = sorted(
        (t.kind, t.field, t.unit, t.block)
        for t in tasks if t.kind in ("h2d", "d2h")
    )
    issued = sorted(
        (t.direction, t.field, t.unit, t.block) for t in live.transfers
    )
    assert issued == graph
    # modeled wire bytes vs real payload bytes: exact for uncompressed
    # units, within 2% for compressed (word-padding of the packed
    # payload is the only difference from the analytic rate)
    modeled = wire_totals(tasks)
    real = live.transfer_summary()
    for d in ("h2d", "d2h"):
        assert real[f"{d}_wire"] == pytest.approx(modeled[d], rel=0.02)


# ----------------------------------------------------------------------
# cross-sweep pipelining + device-resident unit cache
# ----------------------------------------------------------------------

CACHE_BUDGETS = [0, 100_000, 1 << 30]  # off / evicting / everything fits


@pytest.mark.parametrize(
    "schedule", ["paper", "unitgrain", "depth1", "depth2", "depth3"]
)
@pytest.mark.parametrize("budget", CACHE_BUDGETS)
def test_cross_sweep_bit_exact_all_schedules_and_budgets(schedule, budget):
    """≥4 sweeps with the window open across sweep boundaries: output
    must stay bit-identical to the synchronous engine for every
    schedule and cache budget (including 0 = cache off)."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(4))
    sync = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    live = AsyncExecutor(
        cfg, p_prev, p_cur, vel2, schedule=schedule, cache_bytes=budget
    )
    sync.run(4 * BT)
    live.run(4 * BT)
    for name in ("p_cur", "p_prev"):
        np.testing.assert_array_equal(
            live.gather(name), sync.gather(name)
        )


@pytest.mark.parametrize("code", [1, 2])
def test_zero_budget_reduces_to_fetch_every_sweep(code):
    """budget=0 must reproduce the uncached engine exactly: same
    transfer multiset (field, unit, direction, sweep) as the
    synchronous reference, and zero cache activity."""
    sync, live = _pair(code, 4, sweeps=4)
    assert live.stats()["cache"]["hits"] == 0
    assert live.stats()["cache"]["deposits"] == 0
    key = lambda t: (t.direction, t.field, t.unit, t.sweep)
    assert sorted(map(key, live.transfers)) == sorted(
        map(key, sync.transfers)
    )


@pytest.mark.parametrize("code", [1, 2, 4])
def test_cache_hits_emit_no_h2d_record(code):
    """With a budget that holds the full working set, every unit is
    resident after the warmup sweep: steady-state sweeps emit NO h2d
    transfer record at all. Under policy="write-through" d2h
    accounting is untouched (every writeback still materializes — the
    PR 2 semantics, kept reproducible for A/B benchmarking)."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(code))
    sync = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    live = AsyncExecutor(
        cfg, p_prev, p_cur, vel2, cache_bytes=1 << 30,
        policy="write-through",
    )
    sync.run(4 * BT)
    live.run(4 * BT)
    h2d_by_sweep = {}
    for t in live.transfers:
        if t.direction == "h2d":
            h2d_by_sweep[t.sweep] = h2d_by_sweep.get(t.sweep, 0) + 1
    assert h2d_by_sweep.get(0), "warmup sweep must fetch"
    for s in (1, 2, 3):
        assert h2d_by_sweep.get(s, 0) == 0, (s, h2d_by_sweep)
    assert live.stats()["cache"]["hits"] > 0
    assert (
        live.transfer_summary()["d2h_wire"]
        == sync.transfer_summary()["d2h_wire"]
    )
    cache = live.stats()["cache"]
    assert cache["d2h_elided"] == 0 and cache["dirty_bytes"] == 0


def test_steady_state_h2d_wire_beats_paper_schedule():
    """The acceptance bar: with nonzero cache budget, steady-state
    h2d_wire per sweep is strictly lower than the paper schedule
    (cache off) — live and modeled agree on the elision."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(4))

    def per_sweep_h2d(cache_bytes):
        eng = AsyncExecutor(
            cfg, p_prev, p_cur, vel2, schedule="paper",
            cache_bytes=cache_bytes,
        )
        eng.run(4 * BT)
        wire = {}
        for t in eng.transfers:
            if t.direction == "h2d":
                wire[t.sweep] = wire.get(t.sweep, 0) + t.wire_bytes
        return wire

    base = per_sweep_h2d(0)
    cached = per_sweep_h2d(1 << 30)
    for s in (1, 2, 3):  # steady state: strictly fewer wire bytes
        assert cached.get(s, 0) < base[s], (s, cached, base)
    # the modeled replay elides the same transfers
    stats = {}
    tasks = build_sweep_tasks(
        cfg, sweeps=4, schedule="paper", cache_bytes=1 << 30, stats=stats
    )
    modeled = wire_totals(tasks)
    uncached = wire_totals(build_sweep_tasks(cfg, sweeps=4, schedule="paper"))
    assert modeled["h2d"] < uncached["h2d"]
    assert stats["h2d_elided"] > 0


@pytest.mark.parametrize("budget", CACHE_BUDGETS)
def test_live_h2d_matches_cached_multisweep_graph(budget):
    """Model/live agreement under caching: the multi-sweep graph with
    the modeled cache emits exactly the h2d tasks (field, unit, sweep)
    the live executor actually pays for, at every budget."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(2))
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, cache_bytes=budget)
    live.run(4 * BT)
    stats = {}
    tasks = build_sweep_tasks(
        cfg, sweeps=4, schedule="depth2", cache_bytes=budget, stats=stats
    )
    graph = sorted(
        (t.field, t.unit, t.sweep) for t in tasks if t.kind == "h2d"
    )
    issued = sorted(
        (t.field, t.unit, t.sweep)
        for t in live.transfers if t.direction == "h2d"
    )
    assert issued == graph
    live_cache = live.stats()["cache"]
    assert live_cache["hits"] == stats["hits"]
    assert live_cache["evictions"] == stats["evictions"]


def test_window_stays_open_across_sweep_boundary():
    """No sweep-end drain: after a non-final sweep the tail visits are
    still parked (up to depth), and the writebacks land with their own
    sweep number once drained."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(1))
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, schedule="depth2")
    live.sweep()
    assert live.stats()["pending"] == 2  # tail of sweep 0 still parked
    live.sweep()
    live.finish()
    by_sweep = {}
    for t in live.transfers:
        if t.direction == "d2h":
            by_sweep.setdefault(t.sweep, set()).add(t.unit)
    # every writeback attributed to the sweep that produced it
    assert set(by_sweep) == {0, 1}
    assert by_sweep[0] == by_sweep[1]


def test_fetch_after_writeback_hazard_versions():
    """Unit versions: every h2d task of sweep s reads the version the
    previous sweep committed, and each multi-sweep fetch depends on the
    d2h task that produced it (no global barrier)."""
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(2))
    tasks = build_sweep_tasks(cfg, sweeps=3, schedule="unitgrain")
    byid = {t.tid: t for t in tasks}
    for t in tasks:
        if t.kind != "h2d" or t.sweep == 0:
            continue
        key = (t.field, t.unit)
        if cfg.fields[t.field].role == "rw":
            assert t.version == t.sweep  # one writeback per sweep
            wb = [
                byid[d] for d in t.deps
                if byid[d].kind == "d2h"
                and (byid[d].field, byid[d].unit) == key
            ]
            assert len(wb) == 1, t.tid
            assert wb[0].sweep == t.sweep - 1
            assert wb[0].version == t.version
        else:
            assert t.version == 0  # read-only: never rewritten


def test_gather_flushes_pending_window():
    """gather() must see every parked writeback (host consistency)."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(1))
    sync = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2)
    sync.sweep()
    live.sweep()  # tail still parked — gather must drain it
    np.testing.assert_array_equal(
        live.gather("p_cur"), sync.gather("p_cur")
    )


# ----------------------------------------------------------------------
# write-back residency: D2H elision, flush ordering, fault injection
# ----------------------------------------------------------------------


@pytest.mark.parametrize("code", [1, 2, 4])
def test_writeback_elides_steady_state_d2h(code):
    """The PR's acceptance bar: with the working set resident and
    policy="write-back" (the default), NO interior writeback touches
    the wire — d2h_wire is zero for every sweep (commits happen on
    device) — and output is still bit-identical to the synchronous
    engine (gather flushes first). The only d2h the whole run pays is
    the one flush of the final dirty working set at gather."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(code))
    sync = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, cache_bytes=1 << 30)
    sync.run(4 * BT)
    live.run(4 * BT)
    assert live.transfer_summary()["d2h_wire"] == 0
    cache = live.stats()["cache"]
    assert cache["d2h_elided"] > 0
    assert cache["d2h_elided_wire_bytes"] > 0
    assert cache["dirty_bytes"] > 0  # interior state lives on device
    for name in ("p_cur", "p_prev"):
        np.testing.assert_array_equal(
            live.gather(name), sync.gather(name)
        )
    # gather's flush is the only d2h traffic, and it moves the dirty
    # working set exactly once (vs the sync engine's every-sweep cost)
    post = live.transfer_summary()
    assert post["d2h_wire"] == post["d2h_flush_wire"] > 0
    assert post["d2h_wire"] < sync.transfer_summary()["d2h_wire"]
    assert live.stats()["cache"]["dirty_bytes"] == 0


@pytest.mark.parametrize("code,budget", [(2, 0), (2, 100_000),
                                         (2, 1 << 30), (1, 100_000)])
def test_writeback_transfer_log_matches_model(code, budget):
    """Model/live agreement in BOTH directions: the cached multi-sweep
    graph emits exactly the h2d, d2h and flush transfers (field, unit,
    sweep, flush — compared as a multiset, since the live log orders
    by drain time while the graph is topological) the live write-back
    executor pays for, at every budget — including the forced-eviction
    regime (budget 100k) where dirty payloads flush mid-sweep. Flush
    wire bytes must agree exactly (both sides use the real payload
    size); bulk wire totals within the 2% codec-padding slack of the
    analytic model."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(code))
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, cache_bytes=budget)
    live.run(4 * BT)  # run() drains the window; no gather flush yet
    pre_gather = live.stats()["cache"]
    stats = {}
    tasks = build_sweep_tasks(
        cfg, sweeps=4, schedule="depth2", cache_bytes=budget,
        stats=stats,
    )
    graph = sorted(
        (t.kind, t.field, t.unit, t.sweep, t.flush,
         int(t.amount) if t.flush else None)
        for t in tasks if t.kind in ("h2d", "d2h")
    )
    issued = sorted(
        (t.direction, t.field, t.unit, t.sweep, t.flush,
         t.wire_bytes if t.flush else None)
        for t in live.transfers
    )
    assert issued == graph
    modeled = wire_totals(tasks)
    real = live.transfer_summary()
    for d in ("h2d", "d2h"):
        assert real[f"{d}_wire"] == pytest.approx(
            modeled[d], rel=0.02, abs=1
        ), d
    for k in ("hits", "evictions", "flushes", "d2h_elided",
              "d2h_elided_wire_bytes", "dirty_bytes"):
        assert pre_gather[k] == stats[k], k
    if budget == 100_000 and code == 1:
        # the eviction regime really exercised the flush path
        assert stats["flushes"] > 0
        assert any(t.flush for t in tasks)


def test_forced_eviction_mid_sweep_stays_bit_exact():
    """Eviction regime: dirty payloads lose residency mid-sweep and
    flush out of order with the parked window — output must not move
    a bit, and the hazard (no stale host read) holds by construction
    (HostUnitStore.stage asserts host_current on every real fetch)."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(1))
    sync = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, cache_bytes=100_000)
    sync.run(4 * BT)
    live.run(4 * BT)
    assert live.stats()["cache"]["flushes"] > 0
    for name in ("p_cur", "p_prev"):
        np.testing.assert_array_equal(
            live.gather(name), sync.gather(name)
        )


def test_flush_failure_leaves_dirty_for_retry():
    """Fault injection on the flush path (ROADMAP straggler/fault open
    item): a unit's flush that fails mid-gather must leave that entry
    dirty — host state is never silently wrong — and a retry flushes
    exactly the remainder, after which output is bit-exact."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(2))
    sync = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, cache_bytes=1 << 30)
    sync.run(2 * BT)
    live.run(2 * BT)
    dirty_before = live.stats()["cache"]["dirty_bytes"]
    assert dirty_before > 0
    orig_put = live.store.put
    state = {"failed": False}

    def flaky_put(field, kind, idx, value, version=None):
        if not state["failed"]:
            state["failed"] = True
            raise RuntimeError("injected flush failure")
        return orig_put(field, kind, idx, value, version=version)

    live.store.put = flaky_put
    with pytest.raises(RuntimeError):
        live.flush()
    # the failed unit is still dirty; nothing was marked clean early
    assert live.stats()["cache"]["dirty_bytes"] > 0
    assert live.flush() > 0  # retry drains the remainder
    assert live.stats()["cache"]["dirty_bytes"] == 0
    for name in ("p_cur", "p_prev"):
        np.testing.assert_array_equal(
            live.gather(name), sync.gather(name)
        )


def test_writeback_version_commits_without_host_copy():
    """finish() leaves write-back state committed-on-device: the store
    version counters advance with every sweep while the host payload
    version lags until flush — the distinction HostUnitStore now
    tracks."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(2))
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, cache_bytes=1 << 30)
    live.run(3 * BT)
    assert live.store.version_of("p_prev", "R", 1) == 3
    assert live.store.host_version_of("p_prev", "R", 1) == 0
    assert not live.store.host_current("p_prev", "R", 1)
    live.flush()
    assert live.store.host_current("p_prev", "R", 1)
    assert live.store.host_version_of("p_prev", "R", 1) == 3


# ----------------------------------------------------------------------
# host copies started at park
# ----------------------------------------------------------------------

# case -> (policy, cache budget, whether the drain puts each writeback)
HOST_COPY_CASES = {
    "no-residency": ("write-back", 0, True),
    "write-through": ("write-through", 1 << 30, True),
    "refused-deposit": ("write-back", 1, True),  # under one unit
    "resident": ("write-back", 1 << 30, False),
}


@pytest.mark.parametrize("case", sorted(HOST_COPY_CASES))
def test_host_copy_starts_at_park_only_for_writebacks_that_pay(
    case, host_copies
):
    """A writeback that the drain will put has its D2H started as it is
    parked, once, and every such put reports ``early`` and counts in
    ``d2h_early``; a write-back deposit that was stored commits on the
    device and starts no copy. Either way the output is bit-identical
    to the synchronous engine."""
    policy, budget, pays = HOST_COPY_CASES[case]
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, 4, BT, paper_code_fields(4))
    sync = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    live = AsyncExecutor(cfg, p_prev, p_cur, vel2, cache_bytes=budget,
                         policy=policy)
    live.sweep()  # the warm round
    live.finish()
    n0, early0 = len(live.transfers), live.store.wire_stats["d2h_early"]
    started, puts = host_copies
    started.clear(), puts.clear()
    for _ in range(2):
        live.sweep()
    live.finish()
    drained = [t for t in live.transfers[n0:] if t.direction == "d2h"]
    assert not any(t.flush for t in drained)
    early = live.store.wire_stats["d2h_early"] - early0
    if pays:
        assert drained and len(started) == len(drained)
        assert len({id(v) for v in started}) == len(started)
        assert len(puts) == len(drained)
        assert all(m["early"] for m in puts)
        assert early == len(drained)
    else:
        assert not started and not puts and not drained
        assert early == 0
    sync.run(3 * BT)
    for name in ("p_cur", "p_prev"):
        np.testing.assert_array_equal(
            live.gather(name), sync.gather(name)
        )
    if not pays:  # gather's flush puts values it never started
        assert live.store.wire_stats["d2h_early"] == 0
    assert live.stats()["wire"]["d2h_early"] == (
        live.store.wire_stats["d2h_early"])


def test_get_schedule_parsing():
    assert get_schedule("paper").codec_sync
    assert get_schedule("unitgrain").window is None
    assert get_schedule("overlap").codec_sync is False
    assert get_schedule("depth3").window == 3
    assert get_schedule("depth-2").window == 2
    s = depth_k(4)
    assert get_schedule(s) is s
    with pytest.raises(ValueError):
        get_schedule("bogus")
    with pytest.raises(ValueError):
        get_schedule("depth0")
