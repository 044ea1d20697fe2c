"""Multi-device sharded out-of-core executor with compressed halo
exchange.

``ShardedExecutor`` partitions the Z-block decomposition over a device
mesh (``repro.distributed.sharding.partition_domain``) and runs one
full ``AsyncExecutor`` + ``DeviceResidencyManager`` **per shard**, each
pinned to its own (possibly emulated) JAX device. Problem size is then
bounded by host RAM x device count rather than one device's HBM — the
"Beyond 16GB" direction of arXiv 1709.02125, with the source paper's
on-the-fly compression (arXiv 2109.05410) extended to the inter-device
links.

Per round (``kr`` fused sweeps), shards run ascending:

1. shard *d* receives the **held** slices from shard *d-1* — the
   new-time lower halves of the boundary common, computed moments ago
   in this same round (``deliver_held``) — then runs its local sweep
   with its own in-flight window, residency manager, and host store;
   the window stays open across both sweep and shard boundaries (no
   coordinator barrier ever drains it);
2. at the round boundary, each shard's committed left common ships
   right-to-left as a **unit halo** (``deliver_halo``): the *encoded*
   payload (exact ZFP ``Compressed`` bytes for compressed fields)
   lands in the left neighbor's ghost mirror through its host store —
   integrity-checked, versioned ``+kr``, retried under the same
   policies as every other crossing, and wire-logged as op ``"halo"``.

Each shard's sweep is an ``ooc.shard`` span, and each crossing of
either flow one ``ooc.halo.held`` or ``ooc.halo.unit`` span carrying
its wire bytes (``repro.core.spans``). Both flows are recorded as
``Transfer("halo", ...)`` on the *exporting* shard, so per-device
transfer logs compare one-to-one against the per-shard task graphs
(``build_sweep_tasks(shard=...)``) and the merged replay
(``build_sharded_tasks`` / ``pipeline.sharded_timeline``) — model and
live agree on the full transfer multiset including halos.

Numerics are **bit-identical** to the single-device engine: the ghost
fetch decodes the exact unit the neighbor committed, the held import is
the exact slice a single-device run would carry on device, and every
kernel sees the same values in the same op order
(tests/test_sharded.py asserts this across schedules x budgets).

Checkpoints are per-shard with a consistent global cut: ``checkpoint``
is only legal at a round boundary (held inboxes empty, all shards at
the same sweep cursor), where each shard's store holds the entire
distributed state — ``restore`` rebuilds every shard and resumes
bit-identically.

A ``repro.distributed.fault.HeartbeatMonitor`` watches the fleet: every
shard beats once per round, silent or slow shards surface in
``stats()["heartbeat"]`` and accumulate straggler rows in
``recovery_log`` — the silent-shard detection path, reachable from the
engine instead of only from unit tests.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.core import spans
from repro.core.executor import (
    AsyncExecutor,
    _payload_nbytes,
    _payload_raw_bytes,
)
from repro.core.outofcore import OOCConfig
from repro.core.spans import span
from repro.core.taskgraph import (
    Schedule,
    Transfer,
    get_schedule,
    summarize_transfers,
)
from repro.distributed.fault import (
    FaultInjector,
    HeartbeatMonitor,
    ReissuePolicy,
    RetryPolicy,
)
from repro.distributed.sharding import ShardSpec, partition_domain
from repro.kernels.zfp import ops as zfp_ops
from repro.kernels.zfp.ref import Compressed


class ShardedExecutor:
    """Round coordinator over one ``AsyncExecutor`` per domain shard."""

    def __init__(
        self,
        cfg: OOCConfig,
        p_prev: Optional[np.ndarray] = None,
        p_cur: Optional[np.ndarray] = None,
        vel2: Optional[np.ndarray] = None,
        *,
        nshards: int = 2,
        schedule: Union[str, Schedule] = "depth2",
        cache_bytes: int = 0,
        policy: str = "write-back",
        devices: Optional[Sequence] = None,
        mesh=None,
        monitor: Optional[HeartbeatMonitor] = None,
        reissue: Optional[ReissuePolicy] = None,
        retry: Optional[RetryPolicy] = None,
        injector: Optional[FaultInjector] = None,
    ):
        """Partition ``cfg`` over ``nshards`` and build the per-shard
        executors (seeded with the full fields; each store keeps only
        its local unit footprint — per-unit compression is
        deterministic, so subset seeds are bit-identical to a full
        seed's units).

        ``devices``/``mesh`` pin shards to JAX devices (e.g. the
        emulated CPU devices of ``--xla_force_host_platform_device_
        count``); with neither, shards go round-robin onto
        ``jax.local_devices()``, so a four-chip host runs one shard per
        chip and a one-device host shares its device — the same graphs,
        transfers, and results either way. ``cache_bytes``
        is the *per-device* residency budget. ``monitor`` defaults to
        a fresh ``HeartbeatMonitor(nshards)``.
        """
        self.cfg = cfg
        self.schedule = get_schedule(schedule)
        self.temporal = self.schedule.temporal
        self.plan = cfg.temporal_plan(self.temporal)
        if devices is None and mesh is None:
            devices = jax.local_devices()
        self.specs: List[ShardSpec] = partition_domain(
            cfg.ndiv, nshards, devices=devices, mesh=mesh,
        )
        self.shards: List[AsyncExecutor] = []
        for spec in self.specs:
            with self._on(spec):
                self.shards.append(AsyncExecutor(
                    cfg, p_prev, p_cur, vel2,
                    schedule=self.schedule, cache_bytes=cache_bytes,
                    policy=policy, reissue=reissue, retry=retry,
                    injector=injector, shard=spec,
                ))
        self.monitor = (
            monitor if monitor is not None
            else HeartbeatMonitor(nshards)
        )
        # swappable clock (tests drive heartbeat windows with a fake)
        self._timer = time.perf_counter
        self.recovery_log: List[Dict[str, object]] = []
        self.rounds_done = 0
        self.sweeps_done = 0

    @property
    def nshards(self) -> int:
        return len(self.specs)

    @staticmethod
    @contextlib.contextmanager
    def _on(spec: ShardSpec):
        """Run a block under the shard's device pin (no-op unpinned)."""
        if spec.device is None:
            yield
        else:
            with jax.default_device(spec.device):
                yield

    def _log_halo(
        self, exporter: AsyncExecutor, field: str,
        unit: Tuple[str, int], raw: int, wire: int, sweep: int,
        block: int,
    ) -> None:
        """Record one inter-device crossing on the exporting shard —
        the side whose task graph carries the matching halo task."""
        exporter.transfers.append(Transfer(
            "halo", field, unit, raw, wire, sweep, block,
        ))
        exporter.cache.stats.halo_count += 1
        exporter.cache.stats.halo_wire_bytes += wire

    # ------------------------------------------------------------------
    # round loop
    # ------------------------------------------------------------------
    def sweep(self, sweeps: Optional[int] = None) -> None:
        """One round over every shard: ``kr`` fused sweeps per shard
        (defaulting to the schedule's temporal ``k``), the held slices
        flowing left-to-right *within* the round and the encoded
        boundary commons right-to-left at its end. Each shard's
        in-flight window persists across rounds; no global drain."""
        kr = self.temporal if sweeps is None else sweeps
        s0 = self.sweeps_done
        for d, ex in enumerate(self.shards):
            spec = self.specs[d]
            with self._on(spec), span(
                spans.SHARD, shard=d, round=self.rounds_done,
            ):
                ex.sweep(kr)
            self.monitor.beat(d, self.rounds_done, self._timer())
            # the last shard exports no held slices
            for name, val in ex.take_held().items():
                nb = int(val.size) * val.dtype.itemsize
                with span(spans.HALO_HELD, shard=d, field=name, bytes=nb):
                    self.shards[d + 1].deliver_held(name, val)
                    self._log_halo(
                        ex, name, ("C", spec.block_hi - 1), nb, nb, s0,
                        spec.block_hi - 1,
                    )
        for d in range(1, self.nshards):
            ex = self.shards[d]
            spec = self.specs[d]
            for (field, unit), (val, ver) in ex.take_halo().items():
                with self._on(self.specs[d - 1]), span(
                    spans.HALO_UNIT, shard=d, field=field,
                    unit=f"{unit[0]}{unit[1]}", bytes=_payload_nbytes(val),
                ):
                    wire = self.shards[d - 1].deliver_halo(
                        field, unit[0], unit[1], val, ver,
                    )
                    self._log_halo(
                        ex, field, unit, _payload_raw_bytes(val), wire,
                        s0, spec.block_lo,
                    )
        now = self._timer()
        stragglers = self.monitor.stragglers(now)
        if stragglers:
            self.recovery_log.append({
                "kind": "straggler", "round": self.rounds_done,
                "shards": stragglers,
            })
        self.rounds_done += 1
        self.sweeps_done += kr

    def run_sweeps(self, n: int) -> None:
        """Advance ``n`` sweeps in temporal-``k`` rounds (truncated
        final round, same cadence as ``AsyncExecutor.run``)."""
        done = 0
        while done < n:
            kr = min(self.temporal, n - done)
            self.sweep(kr)
            done += kr

    def finish(self) -> None:
        for spec, ex in zip(self.specs, self.shards):
            with self._on(spec):
                ex.finish()

    def flush(self) -> int:
        n = 0
        for spec, ex in zip(self.specs, self.shards):
            with self._on(spec):
                n += ex.flush()
        return n

    # ------------------------------------------------------------------
    # host-side views
    # ------------------------------------------------------------------
    def gather(self, name: str) -> np.ndarray:
        """Reassemble a full field from each unit's *owner* shard (the
        one whose writeback committed it; ghosts are never read — they
        may lag one round at a non-boundary moment)."""
        self.finish()
        self.flush()
        z, y, x = self.cfg.shape
        out = np.zeros(
            (z, y, x), dtype=np.dtype(self.cfg.dtype)
        )
        for spec, ex in zip(self.specs, self.shards):
            units = spec.owned_units()
            vals = [
                ex.store.get(name, kind, idx) for kind, idx in units
            ]
            comp = [
                (u, v) for u, v in zip(units, vals)
                if isinstance(v, Compressed)
            ]
            if comp:
                with self._on(spec):
                    decoded = zfp_ops.decompress_units(
                        [v for _, v in comp], backend=self.cfg.backend,
                    )
                dec = {u: np.asarray(a)
                       for (u, _), a in zip(comp, decoded)}
            else:
                dec = {}
            for (kind, idx), val in zip(units, vals):
                lo, hi = (
                    self.plan.remainder(idx) if kind == "R"
                    else self.plan.common(idx)
                )
                out[lo:hi] = dec.get(
                    (kind, idx), np.asarray(val)
                )
        return out

    @property
    def transfers(self) -> List[Transfer]:
        """All shards' transfer logs, shard-major (halo crossings
        appear once, on their exporter)."""
        out: List[Transfer] = []
        for ex in self.shards:
            out.extend(ex.transfers)
        return out

    def transfer_summary(self) -> Dict[str, object]:
        """Fleet totals plus the per-device breakdown (each entry the
        same dict shape ``summarize_transfers`` gives a single-device
        engine, halo traffic broken out from h2d/d2h)."""
        out: Dict[str, object] = summarize_transfers(self.transfers)
        out["per_device"] = {
            spec.index: summarize_transfers(ex.transfers)
            for spec, ex in zip(self.specs, self.shards)
        }
        return out

    def stats(self) -> Dict[str, object]:
        now = self._timer()
        return {
            "nshards": self.nshards,
            "sweeps": self.sweeps_done,
            "rounds": self.rounds_done,
            "per_device": {
                spec.index: ex.stats()
                for spec, ex in zip(self.specs, self.shards)
            },
            "heartbeat": {
                "stragglers": self.monitor.stragglers(now),
                "dead": self.monitor.dead(now),
                "median_round_time_s": self.monitor.median_step_time(),
                "straggler_rounds": sum(
                    1 for r in self.recovery_log
                    if r.get("kind") == "straggler"
                ),
            },
            "recoveries": list(self.recovery_log),
        }

    # ------------------------------------------------------------------
    # per-shard checkpointing with a consistent global cut
    # ------------------------------------------------------------------
    def checkpoint(
        self,
        directory: str,
        *,
        zstd_level: Optional[int] = None,
        lossy_planes: Optional[int] = None,
        keep: int = 3,
        incremental: bool = True,
    ) -> List[str]:
        """Snapshot every shard under ``<directory>/shard<dd>/``.

        The call is only legal at a round boundary — which is the only
        place ``sweep()`` returns — so the cut is globally consistent
        by construction: all shards sit at the same sweep cursor, every
        held inbox is empty, and each ghost mirror holds exactly the
        version its neighbor committed this round. The union of the
        per-shard stores (owned units only) IS the domain state.

        ``incremental=True`` (default) persists only units whose
        version moved since each shard's previous cut — steady-state
        snapshot bytes shrink to the touched fraction.
        """
        assert not any(ex._held_in for ex in self.shards), (
            "checkpoint mid-round: a held import is pending"
        )
        assert len({ex.sweeps_done for ex in self.shards}) == 1, (
            "inconsistent cut: shards at different sweep cursors"
        )
        paths = []
        for spec, ex in zip(self.specs, self.shards):
            with self._on(spec):
                paths.append(ex.checkpoint(
                    os.path.join(
                        directory, f"shard{spec.index:02d}"
                    ),
                    zstd_level=zstd_level,
                    lossy_planes=lossy_planes,
                    keep=keep, incremental=incremental,
                ))
        return paths

    @classmethod
    def restore(
        cls,
        directory: str,
        *,
        schedule: Union[str, Schedule, None] = None,
        cache_bytes: Optional[int] = None,
        policy: Optional[str] = None,
        devices: Optional[Sequence] = None,
        mesh=None,
        monitor: Optional[HeartbeatMonitor] = None,
        reissue: Optional[ReissuePolicy] = None,
        retry: Optional[RetryPolicy] = None,
        injector: Optional[FaultInjector] = None,
    ) -> "ShardedExecutor":
        """Rebuild every shard from ``<directory>/shard<dd>/`` and
        resume bit-identically. Device pins are process state: pass
        ``devices``/``mesh`` to re-pin on the current topology, or
        neither for round-robin over ``jax.local_devices()`` (the
        shard *layout* comes from the manifests)."""
        root = pathlib.Path(directory)
        subdirs = sorted(
            p for p in root.iterdir()
            if p.is_dir() and p.name.startswith("shard")
        )
        if not subdirs:
            raise FileNotFoundError(
                f"no shard checkpoints under {directory!r}"
            )
        if mesh is not None:
            devices = list(mesh.devices.flat)
        elif devices is None:
            devices = jax.local_devices()
        pins = [devices[d % len(devices)] for d in range(len(subdirs))]
        shards = [
            AsyncExecutor.restore(
                str(p), schedule=schedule, cache_bytes=cache_bytes,
                policy=policy, reissue=reissue, retry=retry,
                injector=injector, device=pin,
            )
            for p, pin in zip(subdirs, pins)
        ]
        specs = [ex.shard for ex in shards]
        assert all(s is not None for s in specs), (
            "restore of a non-sharded checkpoint via ShardedExecutor"
        )
        assert [s.index for s in specs] == list(range(len(specs))), (
            "shard checkpoints out of order or missing"
        )
        self = cls.__new__(cls)
        self.cfg = shards[0].cfg
        self.schedule = shards[0].schedule
        self.temporal = self.schedule.temporal
        self.plan = self.cfg.temporal_plan(self.temporal)
        self.specs = specs
        self.shards = shards
        self.monitor = (
            monitor if monitor is not None
            else HeartbeatMonitor(len(shards))
        )
        self._timer = time.perf_counter
        self.recovery_log = []
        self.sweeps_done = shards[0].sweeps_done
        # every cut lands on a round boundary; rounds resume counting
        # from the sweep cursor (exact for uniform rounds, and only
        # heartbeat labels otherwise)
        self.rounds_done = -(-self.sweeps_done // self.temporal)
        return self
