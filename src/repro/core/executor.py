"""Asynchronous out-of-core executor: cross-sweep pipeline + unit cache.

This is the *live* engine for the paper's core contribution: the
overlap of H2D transfer, GPU codec+stencil work, and D2H transfer
(paper Fig. 4). Where ``repro.core.outofcore.OutOfCoreWave`` runs one
block visit at a time and ``repro.core.pipeline`` only *replays* the
overlap on a modeled timeline, ``AsyncExecutor`` executes the shared
task graph (``repro.core.taskgraph.build_sweep_tasks``) for real:

* every ``h2d`` task stages a host unit onto the device
  (``jnp.asarray`` of the raw planes or of the compressed payload) —
  unless the unit's *current version* is still resident in the device
  unit cache, in which case the transfer is elided entirely;
* every ``decompress``/``stencil``/``compress`` task launches the
  corresponding kernel — all JAX calls here are asynchronously
  dispatched (decompression through the batched ``decompress_units``
  burst), so the device queue runs ahead of the host;
* every ``d2h`` task is *deferred*: the computed (or encoded) unit is
  parked in the in-flight window and only materialized to host memory
  (``np.asarray``, the actual D2H) when the window must drain.

The window is bounded — at most ``depth`` block visits may hold pending
writebacks at once (default 2, i.e. double buffering) — and it stays
**open across sweep boundaries**: there is no sweep-end drain, so block
0 of sweep *s+1* starts fetching while the tail blocks of sweep *s* are
still computing or writing back. Correctness across the boundary rests
on unit *versions* (``HostUnitStore.version_of`` counts committed
writebacks; the executor counts issued ones): a fetch whose newest
version is still parked in the window first drains the window up to
that writeback — the fetch-after-writeback hazard the multi-sweep
graph encodes as dependency edges instead of a global barrier. The
final drain happens in ``run()``/``finish()``/``gather()``.

The device residency manager (``repro.core.unitcache.
DeviceResidencyManager``, dirty-tracking byte-budgeted LRU) owns both
wire directions. The fetch path is PR 2's: writebacks deposit their
on-device ``Compressed`` handle (or raw device array) keyed by the new
version *before* any host materialization, read-only fields deposit on
first fetch, and a fetch whose current version is resident elides the
H2D entirely (no transfer record). Under ``policy="write-back"`` (the
default) the write path is elided symmetrically: a parked writeback
whose dirty deposit was stored never materializes on drain — its
``d2h`` becomes a **version commit with no host copy**
(``HostUnitStore.commit_device``), and the bytes cross the link only
when residency is lost:

* **flush-on-evict** — a dirty LRU victim is materialized immediately
  (``store.put`` + a ``flush`` transfer record), *before* anything can
  refetch it: the fetch-after-writeback hazard holds across pending
  flushes because a fetch either hits the dirty entry or finds the
  flushed (current) host bytes;
* **flush-on-gather / flush-on-demand** — ``flush()`` drains every
  dirty entry to the host store in deterministic LRU order;
  ``gather()`` calls it;
* **flush-on-checkpoint (quiesced)** — the PR 4 checkpoint cut:
  ``checkpoint(dir)`` quiesces the in-flight window (``finish()``),
  runs the ordered ``flush()``, and atomically persists the host
  store payloads + per-unit version vector + executor progress
  through ``repro.checkpoint.checkpoint``; ``AsyncExecutor.
  restore(dir)`` rebuilds the store, the residency manager, and the
  sweep cursor, and resumes **bit-identically** to an uninterrupted
  run (the transfer log differs — residency restarts cold — but not
  one output bit does);
* **overlapped checkpoint cut** — the fifth flush point:
  ``begin_checkpoint(dir)`` (or ``run(..., ckpt_policy=
  CheckpointPolicy(...))`` for periodic every-k-sweeps / wall-budget
  snapshots) freezes the unit-version vector at a sweep boundary
  WITHOUT draining the window: dirty residents are pinned
  copy-on-write in the residency manager and their snapshot D2H
  drains one chunk per block visit of the next sweep through the
  incremental ``repro.checkpoint.ShardWriter`` — the snapshot rides
  the pipeline instead of stalling it, and publishes atomically when
  the last shard lands. Restoring it is indistinguishable from
  restoring a quiesced snapshot of the same boundary.

A straggling or failed flush D2H need not block the snapshot: with a
``repro.distributed.fault.ReissuePolicy`` attached, a failed flush put
is reissued once on the spare stream (``CacheStats.flush_reissues``)
and an over-deadline put is flagged (``flush_stragglers``); the
timeline replay (``repro.core.pipeline.simulate(..., reissue=...)``)
prices the same mitigation on a modeled ``spare`` resource.

``policy="write-through"`` reproduces PR 2 exactly (every writeback
materializes on drain) for A/B runs; ``cache_bytes=0`` (the default)
disables residency and reduces to fetch-and-write-every-sweep.

``docs/architecture.md`` walks the whole unit lifecycle — versions,
dirty bits, the flush points, the checkpoint cut — with a timeline
diagram.

Numerics: the executor issues the *same* JAX ops on the same values as
the synchronous engine — assembly, temporal-blocked stencil, fixed-rate
codec — and the host round-trips it elides (cache-hit fetches,
device-committed writebacks) are byte-preserving, so its output is
bit-identical (tests/test_executor.py) no matter how the overlap
interleaves materialization or how many transfers residency elides.
"""

from __future__ import annotations

import pathlib
import statistics
import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.core import spans
from repro.core.outofcore import HostUnitStore, OOCConfig, unit_shards
from repro.core.ratecontrol import RateController, rate_label
from repro.core.spans import span
from repro.core.taskgraph import (
    Schedule,
    Task,
    Transfer,
    build_sweep_tasks,
    get_schedule,
    summarize_transfers,
)
from repro.core.unitcache import DeviceResidencyManager, Entry
from repro.distributed.fault import (
    FaultError,
    FaultInjector,
    InjectedCrash,
    ReissuePolicy,
    RetryPolicy,
    UnrecoverableFault,
)
from repro.distributed.sharding import ShardSpec
from repro.kernels.stencil import ops as stencil_ops
from repro.kernels.zfp import ops as zfp_ops
from repro.kernels.zfp.ref import Compressed

# manifest schema version of AsyncExecutor.checkpoint payloads
CKPT_FORMAT = 1

UnitKey = Tuple[str, Tuple[str, int]]  # (field, (kind, idx))

# one parked visit: (producing sweep, [(task, value, raw, version)])
_Parked = Tuple[int, List[Tuple[Task, object, int, int]]]


@dataclass
class CheckpointPolicy:
    """Periodic in-loop checkpointing policy for ``AsyncExecutor.run``.

    Consulted at every sweep boundary; a due trigger snapshots the run
    *without stopping it*. Two triggers, combinable (either fires):

    ``every_sweeps``
        snapshot after every k completed sweeps;
    ``wall_budget_s``
        snapshot whenever this much wall time passed since the last
        one (preemption-window checkpointing).

    ``mode`` selects the cut mechanics:

    ``"overlapped"`` (default)
        the overlapped checkpoint cut (``begin_checkpoint``): freeze
        the unit-version vector at the boundary, pin the dirty
        residents (copy-on-write), and drain the snapshot's flush-D2H
        while the next sweep computes — the boundary itself blocks for
        microseconds, not for a quiesce;
    ``"quiesced"``
        the PR 4 cut (``checkpoint``): drain the window, ordered
        flush, one blocking persist — kept for A/B measurement and for
        hosts where snapshot memory pressure (pinned bytes) must be
        zero.

    ``zstd_level``/``keep`` pass through to the persist layer.
    """

    directory: str
    every_sweeps: Optional[int] = None
    wall_budget_s: Optional[float] = None
    mode: str = "overlapped"
    zstd_level: Optional[int] = None
    keep: int = 3

    def __post_init__(self):
        if self.mode not in ("overlapped", "quiesced"):
            raise ValueError(
                f"unknown checkpoint mode {self.mode!r}; "
                "expected 'overlapped' or 'quiesced'"
            )
        if self.every_sweeps is None and self.wall_budget_s is None:
            raise ValueError(
                "CheckpointPolicy needs every_sweeps and/or wall_budget_s"
            )
        if self.every_sweeps is not None and self.every_sweeps < 1:
            raise ValueError(
                f"every_sweeps must be >= 1, got {self.every_sweeps}"
            )

    def due(self, sweeps_done: int, elapsed_s: float) -> bool:
        """Whether a snapshot is due at this sweep boundary.

        ``sweeps_done`` is the boundary index (completed sweeps);
        ``elapsed_s`` the wall time since the previous snapshot (or
        run start).
        """
        if self.every_sweeps and sweeps_done % self.every_sweeps == 0:
            return True
        return (
            self.wall_budget_s is not None
            and elapsed_s >= self.wall_budget_s
        )


@dataclass
class RecoveryPolicy:
    """Automatic restore-from-last-good for ``AsyncExecutor.run``.

    On an *unrecoverable* fault — retry budget exhausted, a checksum
    mismatch with no valid source, an injected crash point — the run
    rolls back to the last published checkpoint under ``directory``
    and replays from there, at most ``max_restarts`` times before the
    fault propagates. If ``directory`` holds no checkpoint when the
    run starts, a baseline snapshot of the entry state is taken first
    (there must be a last-good to roll back *to*). Combine with
    ``ckpt_policy`` for periodic cuts that bound the replay distance.

    Rollback discards all live state the crash would have lost —
    the in-flight window, device residency, any half-drained
    overlapped snapshot (its tmp dir is aborted; the previous
    published checkpoint is untouched) — then reloads the newest
    checkpoint that passes integrity verification (a corrupt latest
    falls back to the previous ``step_<k>``). Replay is
    deterministic, so a recovered run finishes bit-identical to a
    fault-free one; ``CacheStats.recoveries`` / ``replayed_sweeps``
    account the cost.
    """

    directory: str
    max_restarts: int = 3
    zstd_level: Optional[int] = None
    keep: int = 3


def _payload_nbytes(value) -> int:
    """On-wire bytes of a device payload (what a D2H of it would move) —
    matches the analytic ``taskgraph.unit_wire_bytes`` the model uses."""
    if isinstance(value, Compressed):
        return value.nbytes()
    return int(value.size) * value.dtype.itemsize


def _payload_raw_bytes(value) -> int:
    """Uncompressed bytes a device payload represents."""
    if isinstance(value, Compressed):
        n = 1
        for s in value.shape:
            n *= int(s)
        return n * np.dtype(value.dtype).itemsize
    return int(value.size) * value.dtype.itemsize


def _payload_rate(value) -> str:
    """Rate label of a device payload for the per-rate byte gauges."""
    return rate_label(
        value.planes if isinstance(value, Compressed) else None
    )


class AsyncExecutor:
    """Executes the shared out-of-core task graph with a bounded
    in-flight window that spans sweep boundaries, deferred (overlapped)
    writebacks, and a device-resident compressed-unit cache."""

    def __init__(
        self,
        cfg: OOCConfig,
        p_prev: Optional[np.ndarray] = None,
        p_cur: Optional[np.ndarray] = None,
        vel2: Optional[np.ndarray] = None,
        schedule: Union[str, Schedule] = "depth2",
        cache_bytes: int = 0,
        policy: str = "write-back",
        reissue: Optional[ReissuePolicy] = None,
        retry: Optional[RetryPolicy] = None,
        injector: Optional[FaultInjector] = None,
        shard: Optional["ShardSpec"] = None,
        residency=None,
        rates=None,
    ):
        """Build a live executor over ``cfg``.

        Parameters
        ----------
        p_prev, p_cur, vel2:
            Full initial fields, decomposed into host units by
            ``HostUnitStore.seed``. Pass all three, or none of them to
            construct an unseeded executor (``restore`` uses this to
            rebuild the store from a checkpoint instead).
        schedule:
            Issue-order strategy (name or ``Schedule``): ``"paper"``,
            ``"unitgrain"``/``"overlap"``, or ``"depth-k"``. Windowless
            schedules still run double-buffered live (depth 2).
        cache_bytes:
            Device residency budget in bytes for the unit cache.
            ``0`` (default) disables residency: every sweep refetches
            and rewrites every unit.
        policy:
            Residency write policy — ``"write-back"`` (default, elide
            interior D2H; dirty bytes move only at the ordered flush
            points) or ``"write-through"`` (PR 2 semantics, every
            writeback materializes; for A/B runs).
        reissue:
            Optional ``ReissuePolicy``: a failed flush put is reissued
            once on the spare stream instead of aborting the
            gather/checkpoint, and over-deadline puts are counted as
            stragglers. ``None`` keeps the fail-fast behavior.
            (Legacy PR 4 name — a ``ReissuePolicy`` IS a two-attempt
            ``RetryPolicy`` and doubles as one on the wire.)
        retry:
            Optional ``RetryPolicy`` applied to *every* H2D/D2H link
            crossing by the host store (bounded attempts, accounted
            exponential backoff) and to checkpoint shard writes.
            Defaults to ``reissue`` when only that is given, so one
            policy governs all crossings.
        injector:
            Optional ``repro.distributed.fault.FaultInjector``
            replaying a deterministic ``FaultPlan`` on every crossing,
            shard write, and sweep boundary (crash points). The same
            plan drives ``pipeline.simulate(..., faults=plan)`` for
            model/live attempt-multiset parity.
        shard:
            Optional ``repro.distributed.sharding.ShardSpec``
            restricting this executor to one contiguous global block
            range of a multi-device decomposition. The plan stays
            global (tids, spans, versions line up with the
            single-device engine); the store seeds only the local
            unit footprint; the sweep loop walks the local blocks,
            importing the left neighbor's held slice
            (``deliver_held``) and exporting the boundary payloads a
            ``repro.core.sharded.ShardedExecutor`` routes between
            shards.
        residency:
            Optional external residency object used VERBATIM instead of
            constructing a private ``DeviceResidencyManager`` — the
            multi-tenant injection point: ``serving.ooc.
            TenantScheduler`` passes each executor a ``repro.core.
            tenancy.TenantView`` over one shared, arbiter-managed
            manager, so N runs compete for one budget under quota/
            priority arbitration. ``cache_bytes``/``policy`` are
            ignored when this is given (the view carries both).
        rates:
            Optional ``repro.core.ratecontrol.RateController``: each
            unit encodes at its own per-sweep rate (rate ``None`` =
            raw/lossless), the controller observes every writeback's
            round-trip error, and re-decides at sweep boundaries. The
            rate map is persisted in checkpoints and restored
            bit-identically. ``mode="fixed"`` is bit-identical to not
            passing a controller. Not composable with ``shard`` yet
            (halo exports stay spec-rate).
        """
        self.cfg = cfg
        self.schedule = get_schedule(schedule)
        # temporal-k: every visit fuses k sweeps against the halo-k
        # widened plan (validated by OOCConfig with a clear error)
        self.temporal = self.schedule.temporal
        self.plan = cfg.temporal_plan(self.temporal)
        self.plan.check_cover()
        # window=None schedules (paper/unitgrain) still run double-
        # buffered live; the bound is an executor property the
        # depth-k schedules merely make explicit in the graph.
        self.depth = self.schedule.window or 2
        # one policy governs all crossings: ``retry`` if given, else
        # the legacy ``reissue`` (a two-attempt RetryPolicy); the
        # flush spare-stream path keeps consulting ``self.reissue``
        self.reissue = reissue if reissue is not None else retry
        self.retry = retry if retry is not None else reissue
        self.injector = injector
        self.shard = shard
        # local block range (global indices); the whole domain when
        # running single-device
        self._blocks: List[int] = (
            list(shard.blocks) if shard is not None
            else list(range(self.plan.ndiv))
        )
        self.cache = (
            residency if residency is not None
            else DeviceResidencyManager(cache_bytes, policy=policy)
        )
        if rates is not None and shard is not None:
            raise ValueError(
                "rate control does not compose with sharding yet "
                "(halo exports are spec-rate); use mode='fixed' "
                "semantics by passing rates=None"
            )
        self.rates = rates
        self.store = HostUnitStore(
            cfg, plan=self.plan, injector=injector, retry=self.retry,
            stats=self.cache.stats, rates=rates,
        )
        seeds = (p_prev, p_cur, vel2)
        if any(s is not None for s in seeds):
            assert all(s is not None for s in seeds), (
                "seed all three fields or none"
            )
            self.store.seed(
                {"p_prev": p_prev, "p_cur": p_cur, "vel2": vel2},
                keys=self._local_units() if shard is not None else None,
            )
        self.recovery_log: List[Dict[str, object]] = []
        # monotonic clock for flush straggler detection; swappable in
        # tests for deterministic timing
        self._timer = time.perf_counter
        self._flush_times: List[float] = []
        self.transfers: List[Transfer] = []
        self.sweeps_done = 0
        self.max_inflight = 0  # peak block visits with pending D2H
        # the graph depends only on (cfg, schedule, shard), all
        # immutable: build the cache-free single-sweep template once
        # and replay it every sweep (cache hits are a live decision
        # per fetch); sharded templates carry the boundary fetch and
        # the kind-"halo" export tasks
        self._by_block: List[List[Task]] = [
            [] for _ in self._blocks
        ]
        for t in build_sweep_tasks(
            cfg, sweeps=1, schedule=self.schedule, shard=shard,
        ):
            self._by_block[t.block - self._blocks[0]].append(t)

        # halo exchange state (sharded only): the left neighbor's held
        # slices for this round, and the boundary payloads this shard
        # exports (the coordinator routes both)
        self._held_in: Dict[str, jax.Array] = {}
        self._held_out: Dict[str, jax.Array] = {}
        self._halo_out: Dict[UnitKey, Tuple[object, int]] = {}

        # live state
        self._dev: Dict[UnitKey, jax.Array] = {}
        self._staged: Dict[UnitKey, Compressed] = {}
        self._outvals: Dict[UnitKey, jax.Array] = {}
        self._outraw: Dict[UnitKey, int] = {}
        # newest issued (committed or parked) version per unit
        self._ver: Dict[UnitKey, int] = {}
        # visits whose d2h tasks are parked, oldest first; survives
        # sweep boundaries (the cross-sweep window)
        self._pending: Deque[_Parked] = deque()
        # overlapped checkpoint in flight (begin_checkpoint): the
        # incremental shard writer plus the frozen cut's two queues —
        # pinned dirty residents awaiting their snapshot D2H, and
        # host-current payload references awaiting their shard write
        self._ckpt_writer: Optional[ckpt.ShardWriter] = None
        self._ckpt_queue: Deque[Tuple[UnitKey, int]] = deque()
        self._ckpt_host_queue: Deque[
            Tuple[str, str, int, object, int]
        ] = deque()
        self._ckpt_units_meta: Dict[str, Dict[str, object]] = {}
        self._ckpt_extra: Dict[str, object] = {}
        self._ckpt_chunk = 0
        self._ckpt_host_chunk = 0
        self._ckpt_keep = 3
        self._ckpt_cut_sweep = -1
        self._ckpt_expected_units = 0
        self.last_checkpoint_path: Optional[str] = None
        self.ckpt_stats: Dict[str, object] = {
            "snapshots": 0, "overlapped": 0, "quiesced": 0,
            "boundary_block_s": 0.0, "drain_s": 0.0, "shard_bytes": 0,
            "units_reused": 0,
        }

    # ------------------------------------------------------------------
    # halo exchange (sharded executors; routed by ShardedExecutor)
    # ------------------------------------------------------------------
    def _local_units(self) -> List[Tuple[str, int]]:
        """The shard's unit footprint: everything its blocks fetch or
        write, plus the left common its first block assembles from the
        store (the on-device carry a single-device run would hold)."""
        keys = set()
        for i in self._blocks:
            keys.update(self.plan.fetch_units(i))
            keys.update(self.plan.writeback_units(i))
        if self._blocks[0] > 0:
            keys.add(("C", self._blocks[0] - 1))
        return sorted(keys)

    def deliver_held(self, name: str, value: jax.Array) -> None:
        """Accept the left neighbor's held slice (the new-time lower
        half of the boundary common) for the coming round. Must land
        before ``sweep()`` — its first writeback concatenates it."""
        self._held_in[name] = value

    def take_held(self) -> Dict[str, jax.Array]:
        """Pop the held slices this shard exports after a round (empty
        for the last shard)."""
        out, self._held_out = self._held_out, {}
        return out

    def take_halo(self) -> Dict[UnitKey, Tuple[object, int]]:
        """Pop the encoded boundary-common payloads this shard exports
        after a round: ``{(field, unit): (payload, version)}`` (empty
        for the first shard)."""
        out, self._halo_out = self._halo_out, {}
        return out

    def deliver_halo(
        self, field: str, kind: str, idx: int, value, version: int,
    ) -> int:
        """Land a neighbor's halo put in this shard's ghost mirror.
        The crossing goes through the host store as op ``"halo"`` —
        integrity-checked, retried, and wire-logged like any other
        link crossing. Returns wire bytes."""
        wire = self.store.put(
            field, kind, idx, value, version=version, op="halo",
        )
        self._ver[(field, (kind, idx))] = version
        return wire

    # ------------------------------------------------------------------
    # window management
    # ------------------------------------------------------------------
    def _drain_one(self) -> None:
        """Retire the oldest visit's writebacks.

        Write-through: every writeback materializes (blocks on D2H).
        Write-back: a writeback whose payload is still dirty-resident
        commits its version with NO host copy (the d2h the wire never
        sees); one whose payload was evicted has already been flushed
        (the flush committed its newest version, so this drain is a
        no-op); only a payload that never gained residency (deposit
        refused) pays here.
        """
        sweep_no, parked = self._pending.popleft()
        if not parked:  # every writeback of the visit committed early
            return
        with span(spans.DRAIN, round=sweep_no, block=parked[0][0].block):
            for task, value, raw, ver in parked:
                kind, idx = task.unit
                if self.cache.enabled and self.cache.write_back:
                    if self.store.version_of(task.field, kind, idx) >= ver:
                        continue  # an eviction flush already committed it
                    ent = self.cache.peek((task.field, task.unit))
                    if ent is not None and ent.dirty and ent.version >= ver:
                        self.store.commit_device(task.field, kind, idx, ver)
                        continue
                wire = self.store.put(
                    task.field, kind, idx, value, version=ver
                )
                self.transfers.append(Transfer(
                    "d2h", task.field, task.unit, raw, wire,
                    sweep_no, task.block,
                ))

    def _drain_all(self) -> None:
        while self._pending:
            self._drain_one()

    def _admit(self) -> None:
        """Admit a block visit to the window, draining if at depth."""
        while len(self._pending) >= self.depth:
            self._drain_one()

    def _drain_for(self, key: UnitKey) -> None:
        """Fetch-after-writeback hazard: if ``key``'s newest version is
        still parked in the window, drain until the host copy is
        current (the dependency edge the multi-sweep graph encodes)."""
        field, (kind, idx) = key
        while (self._pending and
               self.store.version_of(field, kind, idx)
               < self._ver.get(key, 0)):
            self._drain_one()

    # ------------------------------------------------------------------
    # task actions
    # ------------------------------------------------------------------
    def _exec_h2d(self, task: Task) -> None:
        key = (task.field, task.unit)
        ver = self._ver.get(key, 0)
        if self.cache.enabled:
            hit, cached = self.cache.lookup(key, ver)
            if hit:
                # current version resident on device: H2D elided, no
                # transfer record (the wire sees nothing)
                if isinstance(cached, Compressed):
                    self._staged[key] = cached
                else:
                    self._dev[key] = cached
                return
        self._drain_for(key)
        kind, idx = task.unit
        dev, raw, wire = self.store.stage(task.field, kind, idx)
        if isinstance(dev, Compressed):
            self._staged[key] = dev  # decompress task completes it
        else:
            self._dev[key] = dev
        if self.cache.enabled and self.cfg.fields[task.field].role != "rw":
            # never written back: deposit the fetched payload so later
            # sweeps hit (rw fields deposit at writeback instead)
            res = self.cache.deposit(
                key, ver, dev, wire,
                rate=_payload_rate(dev) if self.rates is not None
                else None,
            )
            for ekey, eent in res.flushes:
                self._flush_entry(ekey, eent, task.block)
        self.transfers.append(Transfer(
            "h2d", task.field, task.unit, raw, wire,
            self.sweeps_done, task.block,
        ))

    def _exec_decompress(self, tasks: List[Task]) -> None:
        """Decode a visit's staged units via the shared batched entry
        point (each jitted decode is async-dispatched either way; this
        keeps the executor on the same code path as gather)."""
        if not tasks:
            return
        # under adaptive rates a unit whose current payload is raw
        # (rate None / lossless) arrives in _dev, not _staged — its
        # template decompress task has nothing to decode
        keys = [
            k for k in ((t.field, t.unit) for t in tasks)
            if k in self._staged
        ]
        with span(spans.DECODE, block=tasks[0].block):
            decoded = zfp_ops.decompress_units(
                [self._staged.pop(k) for k in keys],
                backend=self.cfg.backend,
            )
        for k, arr in zip(keys, decoded):
            self._dev[k] = arr

    def _assemble(self, name: str, i: int,
                  shared: Optional[jax.Array]) -> jax.Array:
        """Fetched (B+2H, Y, X) device field for block i, from staged
        units and the on-device carry — same op sequence as the
        synchronous engine's assembly."""
        plan = self.plan
        h, b = plan.halo, plan.block
        _, y, x = self.cfg.shape
        zeros = lambda n: jnp.zeros(
            (n, y, x), dtype=jnp.dtype(self.cfg.dtype)
        )
        if i == 0:
            first = zeros(h)
        elif shared is not None:
            first = shared
        else:
            # sharded first local block: the left common was fetched
            # (and decompressed) from this shard's own store — the
            # decode of the unit it committed last round, bit-equal to
            # the carry a single-device run keeps on device
            first = self._dev.pop((name, ("C", i - 1)))
        pieces = [first]
        pieces += [self._dev.pop((name, u)) for u in plan.fetch_units(i)]
        if i == plan.ndiv - 1:
            pieces.append(zeros(h))
        out = jnp.concatenate(pieces, axis=0)
        assert out.shape[0] == b + 2 * h, out.shape
        return out

    def _exec_stencil(
        self,
        i: int,
        shared: Dict[str, Optional[jax.Array]],
        held: Dict[str, jax.Array],
        kr: int,
    ) -> Dict[str, Optional[jax.Array]]:
        """Assemble, run ``bt * kr`` fused stencil steps, slice out
        writeback units. Returns the carry (time-t common regions) for
        block i+1. ``kr`` is the number of sweeps this visit fuses
        (== schedule temporal, except a truncated final round)."""
        with span(spans.STENCIL, block=i):
            cfg, plan = self.cfg, self.plan
            h, b = plan.halo, plan.block
            dev: Dict[str, jax.Array] = {}
            new_shared: Dict[str, jax.Array] = {}
            for name in cfg.fields:
                arr = self._assemble(name, i, shared[name])
                if i < plan.ndiv - 1:
                    new_shared[name] = arr[b : b + 2 * h]
                dev[name] = arr
            pp, pc = stencil_ops.fused_temporal_steps(
                dev["p_prev"], dev["p_cur"], dev["vel2"],
                steps=cfg.bt * kr, backend=cfg.backend,
            )
            s, _ = plan.owned(i)
            itemsize = jnp.dtype(cfg.dtype).itemsize
            for name, new in (("p_prev", pp), ("p_cur", pc)):
                owned = new[h : h + b]
                for kind, idx in plan.writeback_units(i):
                    if kind == "R":
                        rlo, rhi = plan.remainder(i)
                        val = owned[rlo - s : rhi - s]
                    else:  # completed C_{i-1}: held lower half + our upper
                        val = jnp.concatenate(
                            [held[name + str(i - 1)], owned[:h]]
                        )
                    self._outvals[(name, (kind, idx))] = val
                    self._outraw[(name, (kind, idx))] = (
                        int(val.size) * itemsize
                    )
                if i < plan.ndiv - 1:
                    held[name + str(i)] = owned[b - h : b]
        return {n: new_shared.get(n) for n in cfg.fields}

    def _exec_compress(self, tasks: List[Task]) -> None:
        """Encode a visit's writeback units via the batched entry point
        (one dispatch burst; units ship as each finishes).

        With a ``RateController`` each unit encodes at its own live
        rate for the round (``rate_for`` at the round-start sweep —
        the same value the graph builder replays); rate-``None`` units
        skip the codec and commit raw, and every encode feeds the
        controller one observation (measured round-trip error at the
        actual rate, and the unit's amplitude)."""
        if not tasks:
            return
        with span(spans.ENCODE, block=tasks[0].block):
            by_planes: Dict[int, List[Task]] = {}
            for t in tasks:
                kind, idx = t.unit
                if self.rates is not None:
                    planes = self.rates.rate_for(
                        t.field, kind, idx, self.sweeps_done
                    )
                else:
                    planes = self.cfg.fields[t.field].planes
                if planes is None:
                    # lossless commit: the raw array ships as-is, error 0
                    val = self._outvals[(t.field, t.unit)]
                    self.rates.observe(
                        t.field, kind, idx, None, 0.0,
                        float(jnp.max(jnp.abs(val))),
                    )
                    continue
                by_planes.setdefault(planes, []).append(t)
            for planes, ts in by_planes.items():
                vals = [self._outvals[(t.field, t.unit)] for t in ts]
                encoded = zfp_ops.compress_units(
                    vals, planes=planes, ndim=3, backend=self.cfg.backend,
                )
                if self.rates is not None:
                    for t, v in zip(ts, vals):
                        kind, idx = t.unit
                        q = zfp_ops.quantize(v, planes=planes, ndim=3)
                        self.rates.observe(
                            t.field, kind, idx, planes,
                            float(jnp.max(jnp.abs(q - v))),
                            float(jnp.max(jnp.abs(v))),
                        )
                for t, c in zip(ts, encoded):
                    self._outvals[(t.field, t.unit)] = c

    def _flush_entry(
        self, key: UnitKey, ent: Entry, block: int, mark: bool = False,
        reissued: bool = False,
    ) -> None:
        """Materialize one dirty payload to the host store and record
        the flush transfer. ``mark`` (the explicit-flush path) clears
        the entry's dirty bit AFTER the put, so a failed put leaves it
        dirty for retry; evicted entries (``mark=False``) were already
        accounted by the manager when they were popped. ``reissued``
        tags the transfer as the spare-stream second attempt."""
        field, (kind, idx) = key
        wire = self.store.put(field, kind, idx, ent.value,
                              version=ent.version)
        if mark:
            self.cache.mark_flushed(key)
        self.transfers.append(Transfer(
            "d2h", field, (kind, idx), _payload_raw_bytes(ent.value),
            wire, self.sweeps_done, block, flush=True, reissued=reissued,
        ))

    def _park_writebacks(self, btasks: List[Task], kr: int = 1) -> None:
        """Bump unit versions (by ``kr`` — one fused visit advances a
        unit ``kr`` sweeps), deposit the on-device payloads into
        residency (dirty under write-back, so the d2h can commit
        without a host copy; the next sweep can hit either way), and
        park the d2h tasks in the window. Dirty LRU victims of the
        deposits flush here — the eviction point."""
        parked: List[Tuple[Task, object, int, int]] = []
        for t in (t for t in btasks if t.kind == "d2h"):
            key = (t.field, t.unit)
            val = self._outvals.pop(key)
            raw = self._outraw.pop(key)
            ver = self._ver.get(key, 0) + kr
            self._ver[key] = ver
            if self.cache.enabled:
                nbytes = _payload_nbytes(val)
                res = self.cache.deposit(
                    key, ver, val, nbytes, dirty=True, bumps=kr,
                    rate=_payload_rate(val) if self.rates is not None
                    else None,
                )
                for ekey, eent in res.flushes:
                    self._flush_entry(ekey, eent, t.block)
                if res.stored and self.cache.write_back:
                    # stored means committed: the manager drops the
                    # superseded entry before its budget check, so
                    # even when adaptive rates change a unit's payload
                    # size across versions, whether THIS deposit is
                    # stored depends only on the new payload and the
                    # budget — a stored deposit can never be displaced
                    # by a refusal, and this writeback will never pay
                    # its own D2H. Account the elision now, in
                    # lockstep with the graph builder.
                    self.cache.note_d2h_elided(nbytes)
            parked.append((t, val, raw, ver))
        if parked:
            self._pending.append((self.sweeps_done, parked))
        self.max_inflight = max(self.max_inflight, len(self._pending))

    # ------------------------------------------------------------------
    # sweep loop
    # ------------------------------------------------------------------
    def sweep(self, sweeps: Optional[int] = None) -> None:
        """One overlapped round over all blocks: ``bt * sweeps`` time
        steps per visit, fused (``sweeps`` defaults to the schedule's
        temporal fusion ``k``; ``run`` passes less on a truncated final
        round). One round = one fetch + one fused stencil + one parked
        writeback (with ``sweeps`` version bumps) per unit.

        No round-end drain: up to ``depth`` tail visits stay parked in
        the window so the next round's head overlaps them. Call
        ``finish()`` (or ``gather()``/``run()``, which do) to force the
        host store consistent.
        """
        kr = self.temporal if sweeps is None else sweeps
        assert 1 <= kr <= self.temporal, (kr, self.temporal)
        with span(spans.ROUND, round=self.sweeps_done, sweeps=kr):
            plan = self.plan
            rw = [n for n, sp in self.cfg.fields.items() if sp.role == "rw"]
            held: Dict[str, jax.Array] = {}
            if self.shard is not None and not self.shard.first:
                # the left neighbor's held slices seed the boundary
                # writeback concat exactly as block lo-1's visit would
                lo = self._blocks[0]
                for n in rw:
                    held[n + str(lo - 1)] = self._held_in.pop(n)
            shared: Dict[str, Optional[jax.Array]] = {
                n: None for n in self.cfg.fields
            }
            for j, i in enumerate(self._blocks):
                with span(spans.VISIT, round=self.sweeps_done, block=i):
                    btasks = self._by_block[j]
                    # window admission precedes this visit's first
                    # transfer
                    self._admit()
                    # one chunk of an in-flight overlapped snapshot
                    # drains here, interleaved with this visit's
                    # fetch/compute — the snapshot's flush-D2H rides the
                    # sweep instead of stalling it (same cadence the
                    # checkpoint-aware graph replays)
                    self._drain_ckpt(paced=True)
                    for t in (t for t in btasks if t.kind == "h2d"):
                        self._exec_h2d(t)
                    self._exec_decompress(
                        [t for t in btasks if t.kind == "decompress"]
                    )
                    shared = self._exec_stencil(i, shared, held, kr)
                    self._exec_compress(
                        [t for t in btasks if t.kind == "compress"]
                    )
                    # capture the boundary-common export BEFORE parking
                    # pops the payload: the halo ships the same encoded
                    # object the writeback commits, at the version the
                    # park will issue
                    for t in btasks:
                        if t.kind == "halo" and ".halo." in t.tid:
                            key = (t.field, t.unit)
                            self._halo_out[key] = (
                                self._outvals[key],
                                self._ver.get(key, 0) + kr,
                            )
                    self._park_writebacks(btasks, kr)
            if self.shard is not None and not self.shard.last:
                last = self._blocks[-1]
                self._held_out = {n: held[n + str(last)] for n in rw}
            assert not self._dev and not self._staged and not self._outvals
            self.sweeps_done += kr
            if self.rates is not None:
                # sweep boundary: re-decide the rate map from this
                # round's observations (applies from the next sweep on)
                # — the same point the synchronous engine decides, so
                # both engines record identical decision logs
                self.rates.decide(self.sweeps_done)

    def finish(self) -> None:
        """Drain the window: every issued writeback is *committed* —
        on host (write-through / lost residency) or on device
        (write-back commits). Dirty-resident payloads stay resident;
        call ``flush()`` (or ``gather()``, which does) before any
        host-side read of the store. An in-flight overlapped snapshot
        is force-completed first."""
        with span(spans.FINISH):
            self._drain_ckpt()
            self._drain_all()

    def flush(self) -> int:
        """Flush-on-demand: materialize every dirty-resident payload to
        the host store, oldest (LRU) first — the deterministic flush
        order. Entries stay resident (clean) so later sweeps still hit.
        ``gather()`` and ``checkpoint()`` call this. Returns the number
        of units flushed.

        Fault behavior: without a ``reissue`` policy, a failed put
        raises and leaves its entry dirty, so a retry flushes exactly
        the remainder. With ``reissue`` set, a failed put is reissued
        once on the spare stream (``CacheStats.flush_reissues``) so a
        single transient fault cannot stall a snapshot, and a put
        slower than ``reissue.deadline(median of previous flushes)`` is
        counted in ``CacheStats.flush_stragglers`` (the timeline model
        prices the corresponding spare-stream win — see
        ``repro.core.pipeline.simulate``).
        """
        with span(spans.FLUSH):
            self._drain_ckpt()  # release snapshot pins before flushing
            n = 0
            for key, ent in self.cache.dirty_entries():
                t0 = self._timer()
                reissued = False
                try:
                    self._flush_entry(key, ent, -1, mark=True)
                except Exception:
                    if self.reissue is None:
                        raise
                    # spare-stream reissue: the straggling/failed attempt
                    # is abandoned and the payload re-put once; a second
                    # failure propagates (the entry stays dirty for retry)
                    self._flush_entry(key, ent, -1, mark=True, reissued=True)
                    self.cache.stats.flush_reissues += 1
                    reissued = True
                elapsed = self._timer() - t0
                # a reissued put already counted as a fault: its two-
                # attempt elapsed neither flags a straggler nor enters the
                # rolling median (it would inflate the baseline)
                if not reissued:
                    if (
                        self.reissue is not None
                        and self._flush_times
                        and self.reissue.should_reissue(
                            elapsed, statistics.median(self._flush_times)
                        )
                    ):
                        self.cache.stats.flush_stragglers += 1
                    self._flush_times.append(elapsed)
                    if len(self._flush_times) > 64:  # rolling window
                        self._flush_times.pop(0)
                n += 1
        return n

    def run(
        self,
        total_steps: int,
        ckpt_policy: Optional[CheckpointPolicy] = None,
        recovery: Optional[RecoveryPolicy] = None,
    ) -> None:
        """Advance the run by ``total_steps`` (a multiple of ``bt``).

        With ``ckpt_policy`` the loop consults the policy at every
        sweep boundary and snapshots when due — overlapped (default:
        the cut pins and the flush-D2H rides the next sweep) or
        quiesced per ``policy.mode``. The final ``finish()`` completes
        any snapshot still draining, so ``run`` always returns with
        the last due checkpoint published (``last_checkpoint_path``).

        With ``recovery`` the run is *self-healing*: an unrecoverable
        fault (retries exhausted, checksum mismatch with no valid
        source, an injected crash point) rolls the executor back to
        the last published checkpoint under ``recovery.directory`` and
        replays, up to ``recovery.max_restarts`` times. A baseline
        snapshot is taken at entry when the directory holds none.
        Replay is deterministic: a recovered run's output is
        bit-identical to a fault-free one (tests/test_chaos.py).
        """
        assert total_steps % self.cfg.bt == 0
        target = self.sweeps_done + total_steps // self.cfg.bt
        restarts = 0
        while True:
            try:
                if recovery is not None and ckpt.latest(
                    recovery.directory
                ) is None:
                    # a rollback needs a last-good to roll back TO
                    self.checkpoint(
                        recovery.directory,
                        zstd_level=recovery.zstd_level,
                        keep=recovery.keep,
                    )
                self._run_to(target, ckpt_policy)
                return
            except FaultError as e:
                if (
                    recovery is None
                    or restarts >= recovery.max_restarts
                    or ckpt.latest(recovery.directory) is None
                ):
                    raise
                restarts += 1
                self._rollback(recovery.directory, e)

    def advance_round(self, target: int) -> int:
        """Advance ONE temporal round toward ``target`` completed
        sweeps — the cooperative yield point at a round boundary.

        ``run``'s loop is built from this, and the multi-tenant
        ``serving.ooc.TenantScheduler`` drives each tenant's executor
        one ``advance_round`` at a time in the deterministic
        ``tenancy.interleave_rounds`` order. Returns the number of
        sweeps advanced (``0`` when already at ``target``); raises
        ``InjectedCrash`` when the injector has a crash point due at
        the new boundary."""
        if self.sweeps_done >= target:
            return 0
        # truncated final round: fuse only what remains
        kr = min(self.temporal, target - self.sweeps_done)
        self.sweep(kr)
        if self.injector is not None and self.injector.crash_point(
            self.sweeps_done
        ):
            raise InjectedCrash(
                f"injected crash at sweep boundary "
                f"{self.sweeps_done}"
            )
        return kr

    def _run_to(
        self, target: int, ckpt_policy: Optional[CheckpointPolicy]
    ) -> None:
        """The sweep loop proper: advance to ``target`` completed
        sweeps, consulting ``ckpt_policy`` and the injector's crash
        points at every boundary, then drain."""
        last_ckpt = self._timer()
        while self.sweeps_done < target:
            self.advance_round(target)
            if ckpt_policy is not None and ckpt_policy.due(
                self.sweeps_done, self._timer() - last_ckpt
            ):
                t0 = self._timer()
                if ckpt_policy.mode == "quiesced":
                    self.checkpoint(
                        ckpt_policy.directory,
                        zstd_level=ckpt_policy.zstd_level,
                        keep=ckpt_policy.keep,
                    )
                else:
                    self.begin_checkpoint(
                        ckpt_policy.directory,
                        zstd_level=ckpt_policy.zstd_level,
                        keep=ckpt_policy.keep,
                    )
                self.ckpt_stats["boundary_block_s"] += (
                    self._timer() - t0
                )
                last_ckpt = self._timer()
        self.finish()

    # ------------------------------------------------------------------
    # rollback-and-replay (the recovery loop)
    # ------------------------------------------------------------------
    def _rollback(self, directory: str, cause: Exception) -> None:
        """Reset to the last-good checkpoint under ``directory``.

        Discards everything the fault would have lost on a real crash
        — the in-flight window, staged/parked device values, device
        residency, any half-drained overlapped snapshot (aborted; its
        tmp dir vanishes and the previously *published* checkpoint is
        untouched) — then reloads the newest checkpoint that passes
        integrity verification, falling back to earlier ``step_<k>``
        directories if the latest is corrupt.
        """
        if self._ckpt_writer is not None:
            self._ckpt_writer.abort()
            self._ckpt_writer = None
        self._ckpt_queue.clear()
        self._ckpt_host_queue.clear()
        self._ckpt_units_meta = {}
        self._pending.clear()
        self._dev.clear()
        self._staged.clear()
        self._outvals.clear()
        self._outraw.clear()
        self._flush_times.clear()
        # cold residency (device state died with the "process"), same
        # cumulative stats surface; the byte gauges reset with it. A
        # TenantView's rollback_reset drops only ITS tenant from the
        # shared manager — other tenants' residency survives the crash.
        self.cache = self.cache.rollback_reset()
        stats = self.cache.stats
        self.store.stats = stats
        step, leaves, extra, path = self._load_last_good(directory)
        self.store.load_state(leaves, extra["store"])
        prior = self.sweeps_done
        self.sweeps_done = int(extra["progress"]["sweeps_done"])
        self._ver = {
            (u["field"], (u["kind"], int(u["idx"]))): int(u["version"])
            for u in extra["store"]["units"].values()
            if int(u["version"]) > 0
        }
        stats.recoveries += 1
        stats.replayed_sweeps += max(0, prior - self.sweeps_done)
        self.recovery_log.append({
            "fault": f"{type(cause).__name__}: {cause}",
            "from_sweep": prior,
            "resumed_at": self.sweeps_done,
            "checkpoint": path,
        })

    @staticmethod
    def _load_last_good(directory: str):
        """Newest checkpoint under ``directory`` that passes manifest,
        shard, and unit-digest verification; corrupt ones are skipped
        (newest-first) so one rotten snapshot cannot strand the run."""
        base = pathlib.Path(directory)
        candidates = sorted(
            (p for p in base.iterdir() if p.name.startswith("step_")),
            reverse=True,
        ) if base.exists() else []
        last: Optional[Exception] = None
        for p in candidates:
            try:
                step, leaves, extra = ckpt.load(str(p))
                return step, leaves, extra, str(p)
            except FaultError as e:  # corrupt: try the previous cut
                last = e
        raise UnrecoverableFault(
            f"no loadable checkpoint under {directory!r} to roll "
            f"back to: {last}"
        ) from last

    # ------------------------------------------------------------------
    # overlapped periodic checkpointing (the fifth flush point)
    # ------------------------------------------------------------------
    def _progress_extra(self) -> Dict[str, object]:
        """Manifest ``extra`` payload shared by both checkpoint cuts:
        config + executor progress (store meta is appended by each)."""
        return {
            "format": CKPT_FORMAT,
            "kind": "ooc-executor",
            "cfg": self.cfg.to_dict(),
            "progress": {
                "sweeps_done": self.sweeps_done,
                "schedule": self.schedule.name,
                # full strategy fields, so a custom Schedule object
                # (not resolvable by name) still restores
                "schedule_spec": {
                    "name": self.schedule.name,
                    "codec_sync": self.schedule.codec_sync,
                    "window": self.schedule.window,
                    "temporal": self.schedule.temporal,
                },
                "depth": self.depth,
                "cache_bytes": self.cache.budget_bytes,
                "policy": self.cache.policy,
                # sharded layout (None single-device); device pins are
                # process state and never persist
                "shard": (
                    self.shard.to_dict()
                    if self.shard is not None else None
                ),
            },
            # adaptive rate control: the full policy snapshot (decision
            # log + pending observations), restored bit-identically so
            # a resumed run re-decides exactly what this one would have
            **(
                {"rates": self.rates.state_dict()}
                if self.rates is not None else {}
            ),
        }

    def _early_commit_parked(self) -> None:
        """Commit every parked writeback that has NO dirty residency to
        the host store, without draining the window.

        Part of the overlapped cut: a parked payload whose bytes are
        dirty-resident will be captured through its (pinned) cache
        entry, but one whose deposit was refused (budget 0/too small)
        or whose policy is write-through exists only in the window — so
        its ordinary d2h happens *now* (the same put, the same transfer
        record, just earlier than its drain) and the snapshot reads the
        host bytes. The window stays parked: visits keep overlapping.
        """
        for i, (sweep_no, parked) in enumerate(self._pending):
            kept: List[Tuple[Task, object, int, int]] = []
            for task, value, raw, ver in parked:
                kind, idx = task.unit
                key = (task.field, task.unit)
                if self.store.version_of(task.field, kind, idx) >= ver:
                    continue  # an eviction flush already committed it
                if self.cache.enabled and self.cache.write_back:
                    ent = self.cache.peek(key)
                    if ent is not None and ent.dirty and ent.version >= ver:
                        kept.append((task, value, raw, ver))
                        continue  # snapshot pins the dirty resident
                wire = self.store.put(
                    task.field, kind, idx, value, version=ver
                )
                self.transfers.append(Transfer(
                    "d2h", task.field, task.unit, raw, wire,
                    sweep_no, task.block,
                ))
            self._pending[i] = (sweep_no, kept)

    def begin_checkpoint(
        self,
        directory: str,
        *,
        zstd_level: Optional[int] = None,
        keep: int = 3,
    ) -> None:
        """The **overlapped checkpoint cut** — snapshot a live run at a
        sweep boundary *without draining the in-flight window*.

        The cut freezes the unit-version vector at this boundary and
        classifies every unit:

        * **host-current** — the committed payload is on host: its
          object reference is captured (puts replace, never mutate) and
          the shard write is deferred;
        * **dirty-resident** — the committed payload lives only on
          device: the entry is **pinned** in the residency manager
          (copy-on-write — a newer writeback shadows the pre-cut
          payload instead of dropping it, and eviction skips it) and
          its snapshot D2H joins the background flush queue;
        * **parked-without-residency** — committed early
          (``_early_commit_parked``): its ordinary d2h just happens at
          the cut instead of at drain.

        The boundary call itself does no D2H and no file IO — it
        blocks for the classification only. The queues then drain as
        ordinary paced transfers overlapping the next sweep's
        fetch/compute (a chunk per block visit), through the
        incremental ``repro.checkpoint.ShardWriter``; the snapshot
        publishes (atomic ``os.replace``) when the last shard lands.
        ``finish()``/``flush()``/``gather()``/``checkpoint()`` and a
        subsequent cut all force-complete an in-flight snapshot first.

        The persisted snapshot is indistinguishable from a quiesced
        ``checkpoint()`` taken at the same boundary: ``restore``
        resumes bit-identically from either.
        """
        self._drain_ckpt()  # at most one snapshot in flight
        self._early_commit_parked()
        self._ckpt_extra = self._progress_extra()
        self._ckpt_writer = ckpt.ShardWriter(
            directory, self.sweeps_done,
            zstd_level=zstd_level, extra=self._ckpt_extra,
            injector=self.injector, retry=self.retry,
            stats=self.cache.stats,
        )
        self._ckpt_keep = keep
        self._ckpt_cut_sweep = self.sweeps_done - 1
        self._ckpt_units_meta = {}
        unit_keys = self.store.unit_keys()
        self._ckpt_expected_units = len(unit_keys)
        for (field, kind, idx) in unit_keys:
            key: UnitKey = (field, (kind, idx))
            ver = self._ver.get(
                key, self.store.version_of(field, kind, idx)
            )
            if self.store.host_version_of(field, kind, idx) >= ver:
                # capture the host payload reference NOW: a later
                # flush would replace it with a newer version
                self._ckpt_host_queue.append(
                    (field, kind, idx,
                     self.store.host_payload(field, kind, idx, ver),
                     ver)
                )
            # else: committed-ahead-of-host implies dirty-resident
            # (early commit handled the rest) — pinned below, in LRU
            # order so the checkpoint-aware graph replays the same
            # pin/release sequence on the shared policy object
        for key, ent in self.cache.dirty_entries():
            field, (kind, idx) = key
            ver = self._ver.get(key, 0)
            # the dirty resident must BE the frozen cut version, and
            # the host must still lack it (host_current() is about the
            # *committed* version, which may lag the parked cut)
            assert (
                ent.version == ver
                and self.store.host_version_of(field, kind, idx) < ver
            ), ("overlapped cut: dirty resident out of step", key, ver)
            self.cache.pin(key)
            self._ckpt_queue.append((key, ver))
        assert (
            len(self._ckpt_queue) + len(self._ckpt_host_queue)
            == self._ckpt_expected_units
        ), "overlapped cut must cover every unit exactly once"
        ndiv = self.plan.ndiv
        self._ckpt_chunk = -(-len(self._ckpt_queue) // ndiv)
        self._ckpt_host_chunk = -(-len(self._ckpt_host_queue) // ndiv)

    def _drain_ckpt(self, paced: bool = False) -> None:
        """Advance the in-flight snapshot: materialize pinned payloads
        into shards (the snapshot's flush-D2H) and write deferred
        host-current shards. ``paced`` processes one chunk of each
        queue (the per-block-visit cadence that spreads the snapshot
        across the next sweep); otherwise everything drains and the
        snapshot publishes."""
        if self._ckpt_writer is None:
            return
        t0 = self._timer()
        n_flush = self._ckpt_chunk if paced else len(self._ckpt_queue)
        for _ in range(min(n_flush, len(self._ckpt_queue))):
            key, ver = self._ckpt_queue.popleft()
            ent = self.cache.pinned_entry(key)
            assert ent is not None and ent.version == ver, (key, ver)
            field, (kind, idx) = key
            self._write_unit_shards(field, kind, idx, ent.value, ver)
            wire = _payload_nbytes(ent.value)
            raw = _payload_raw_bytes(ent.value)
            # releasing the pin re-enforces the budget: evicted dirty
            # victims of the pin pressure flush to host here
            for ekey, eent in self.cache.release(key):
                self._flush_entry(ekey, eent, -1)
            self.cache.note_ckpt_flush(wire)
            self.transfers.append(Transfer(
                "d2h", field, (kind, idx), raw, wire,
                self._ckpt_cut_sweep, -1, ckpt=True,
            ))
        n_host = (
            self._ckpt_host_chunk if paced
            else len(self._ckpt_host_queue)
        )
        for _ in range(min(n_host, len(self._ckpt_host_queue))):
            field, kind, idx, value, ver = (
                self._ckpt_host_queue.popleft()
            )
            self._write_unit_shards(field, kind, idx, value, ver)
        self.ckpt_stats["drain_s"] += self._timer() - t0
        if not self._ckpt_queue and not self._ckpt_host_queue:
            self._finalize_ckpt()

    def _write_unit_shards(
        self, field: str, kind: str, idx: int, value, ver: int,
    ) -> None:
        """One unit into the in-flight snapshot: durable shard
        write(s) + the manifest meta entry."""
        leaves, meta = unit_shards(field, kind, idx, value, ver)
        for lkey, arr in leaves.items():
            self.ckpt_stats["shard_bytes"] += (
                self._ckpt_writer.add(lkey, arr)
            )
        self._ckpt_units_meta[f"{field}.{kind}{idx}"] = meta

    def _finalize_ckpt(self) -> None:
        """Publish the overlapped snapshot (atomic rename + gc)."""
        # re-verify the cut's coverage at publish time: if a shard
        # write failed mid-drain and the driver swallowed it, refuse
        # to publish an incomplete snapshot (the previous complete one
        # stays live and is never gc'd by this writer)
        assert len(self._ckpt_units_meta) == self._ckpt_expected_units, (
            "incomplete overlapped snapshot: refusing to publish",
            len(self._ckpt_units_meta), self._ckpt_expected_units,
        )
        extra = dict(self._ckpt_extra)
        extra["store"] = {"units": self._ckpt_units_meta}
        self._ckpt_writer.set_extra(extra)
        self.last_checkpoint_path = self._ckpt_writer.finalize(
            keep=self._ckpt_keep
        )
        self._ckpt_writer = None
        self._ckpt_units_meta = {}
        self.ckpt_stats["snapshots"] += 1
        self.ckpt_stats["overlapped"] += 1

    # ------------------------------------------------------------------
    # crash-consistent checkpoint / restore
    # ------------------------------------------------------------------
    def checkpoint(
        self,
        directory: str,
        *,
        zstd_level: Optional[int] = None,
        lossy_planes: Optional[int] = None,
        keep: int = 3,
        incremental: bool = False,
    ) -> str:
        """Crash-consistent snapshot of the in-flight run — one call.

        The checkpoint cut (the fourth flush point) runs in order:

        1. **quiesce** — ``finish()`` drains the in-flight window, so
           every issued writeback is committed (on host, or on device
           as a dirty resident);
        2. **ordered flush** — ``flush()`` materializes every dirty
           resident to the host store, LRU-first; with a ``reissue``
           policy a straggling/failed flush is reissued on the spare
           stream instead of stalling the snapshot;
        3. **atomic persist** — the host store payloads, the per-unit
           version vector, and the executor progress (sweep cursor,
           schedule, residency policy + budget) go through
           ``repro.checkpoint.checkpoint.save`` (sharded leaves,
           tmp-dir + fsync + ``os.replace``, zstd when available or
           raw otherwise, optionally lossy-ZFP f32 leaves via
           ``lossy_planes``).

        Returns the final checkpoint path (``<directory>/step_<k>``
        where ``k`` is the sweep index). ``AsyncExecutor.restore``
        rebuilds a live executor from it that resumes bit-identically
        to an uninterrupted run.

        With ``incremental=True`` (differential snapshot) units whose
        committed version did not move since the previous cut in
        ``directory`` are not re-encoded or rewritten: their manifest
        entries point back (via an external ``dir`` reference, chains
        flattened to the original writer) at the earlier checkpoint's
        shard files, and the reference-aware gc keeps those source
        directories alive while any retained manifest needs them. The
        restored state is identical either way; only write volume
        changes — ``ckpt_stats["units_reused"]`` counts the skips.
        """
        self.finish()
        self.flush()
        leaves, store_meta = self.store.state_dict()
        extra = self._progress_extra()
        extra["store"] = store_meta
        prev_leaves: Dict[str, Dict[str, object]] = {}
        prev_units: Dict[str, Dict[str, object]] = {}
        prev_dir = None
        if incremental:
            found = ckpt.latest(directory)
            if found is not None:
                try:
                    prev = ckpt.read_manifest(found)
                except Exception:
                    prev = None  # unreadable previous cut: full snapshot
                if prev is not None:
                    prev_dir = pathlib.Path(found).name
                    prev_leaves = prev.get("leaves", {})
                    prev_units = (
                        prev.get("extra", {}).get("store", {})
                        .get("units", {})
                    )
        unchanged = {
            ukey for ukey, u in store_meta["units"].items()
            if ukey in prev_units
            and int(prev_units[ukey]["version"]) == int(u["version"])
        }
        w = ckpt.ShardWriter(
            directory, self.sweeps_done, zstd_level=zstd_level,
            lossy_planes=lossy_planes, extra=extra,
            injector=self.injector, retry=self.retry,
            stats=self.cache.stats,
        )
        reused = 0
        try:
            for key, leaf in leaves.items():
                ukey = key
                for suf in (".payload", ".emax"):
                    if key.endswith(suf):
                        ukey = key[: -len(suf)]
                ent = prev_leaves.get(key)
                if ukey in unchanged and ent is not None:
                    w.add_external(key, ent, prev_dir)
                    reused += 1
                else:
                    w.add(key, leaf)
        except BaseException:
            w.abort()
            raise
        path = w.finalize(keep=keep)
        self.last_checkpoint_path = path
        self.ckpt_stats["snapshots"] += 1
        self.ckpt_stats["quiesced"] += 1
        self.ckpt_stats["units_reused"] += reused
        return path

    @classmethod
    def restore(
        cls,
        directory: str,
        *,
        schedule: Union[str, Schedule, None] = None,
        cache_bytes: Optional[int] = None,
        policy: Optional[str] = None,
        reissue: Optional[ReissuePolicy] = None,
        retry: Optional[RetryPolicy] = None,
        injector: Optional[FaultInjector] = None,
        device=None,
    ) -> "AsyncExecutor":
        """Rebuild a live executor from ``checkpoint()`` state.

        ``directory`` may be a checkpoint root (the latest
        ``step_<k>`` is used) or one specific checkpoint path. The
        host unit store, per-unit version vector, and sweep cursor are
        restored exactly; device residency restarts cold (it is device
        state, gone with the process), so the first resumed sweep
        refetches its working set — transfer counts differ from an
        uninterrupted run, output does not: the resumed run is
        bit-identical across schedules and cache policies
        (tests/test_checkpoint_restore.py).

        ``schedule``/``cache_bytes``/``policy`` default to the values
        the checkpoint recorded; pass overrides to resume under a
        different execution strategy (allowed because none of them
        affect numerics). A sharded executor's layout restores from
        the manifest; ``device`` optionally re-pins it (device pins
        are process state and never persist).
        """
        path = pathlib.Path(directory)
        if not (path / "manifest.json").exists():
            found = ckpt.latest(directory)
            if found is None:
                raise FileNotFoundError(
                    f"no checkpoint under {directory!r}"
                )
            path = pathlib.Path(found)
        step, leaves, extra = ckpt.load(str(path))
        if extra.get("kind") != "ooc-executor":
            raise ValueError(
                f"{path} is not an AsyncExecutor checkpoint "
                f"(kind={extra.get('kind')!r})"
            )
        prog = extra["progress"]
        if schedule is None:
            try:
                schedule = get_schedule(prog["schedule"])
            except ValueError:
                # a custom (non-builtin) Schedule: rebuild from the
                # persisted strategy fields
                spec = prog["schedule_spec"]
                schedule = Schedule(
                    spec["name"], codec_sync=spec["codec_sync"],
                    window=spec["window"],
                    temporal=spec.get("temporal", 1),
                )
        shard_d = prog.get("shard")
        cfg = OOCConfig.from_dict(extra["cfg"])
        rates = (
            RateController.from_state(cfg, extra["rates"])
            if "rates" in extra else None
        )
        ex = cls(
            cfg,
            schedule=schedule,
            cache_bytes=(
                prog["cache_bytes"] if cache_bytes is None
                else cache_bytes
            ),
            policy=prog["policy"] if policy is None else policy,
            reissue=reissue, retry=retry, injector=injector,
            shard=(
                ShardSpec.from_dict(shard_d, device=device)
                if shard_d else None
            ),
            rates=rates,
        )
        ex.store.load_state(leaves, extra["store"])
        ex.sweeps_done = int(prog["sweeps_done"])
        # newest issued version == committed version at the cut (the
        # window was drained and every dirty resident flushed)
        ex._ver = {
            (u["field"], (u["kind"], int(u["idx"]))): int(u["version"])
            for u in extra["store"]["units"].values()
            if int(u["version"]) > 0
        }
        return ex

    # ------------------------------------------------------------------
    def gather(self, name: str) -> np.ndarray:
        self.finish()
        self.flush()
        return self.store.gather(name)

    def transfer_summary(self) -> Dict[str, int]:
        return summarize_transfers(self.transfers)

    def stats(self) -> Dict[str, object]:
        return {
            "depth": self.depth,
            "max_inflight": self.max_inflight,
            "sweeps": self.sweeps_done,
            "pending": len(self._pending),
            "policy": self.cache.policy,
            "cache": self.cache.stats.as_dict(),
            "cache_bytes_used": self.cache.bytes_used,
            "cache_peak_bytes": self.cache.peak_bytes,
            "cache_dirty_bytes": self.cache.dirty_bytes,
            "checkpoint": dict(self.ckpt_stats),
            "ckpt_pending_units": (
                len(self._ckpt_queue) + len(self._ckpt_host_queue)
            ),
            # the self-healing wire: store-side retry/integrity
            # counters, accounted backoff, injector fire counts, and
            # the rollback-and-replay history
            "wire": dict(self.store.wire_stats),
            "wire_backoff_s": self.store.backoff_s,
            "injected": (
                dict(self.injector.counts)
                if self.injector is not None else {}
            ),
            "recoveries": list(self.recovery_log),
        }
