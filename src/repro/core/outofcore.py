"""Out-of-core stencil engines with separate on-device compression.

The paper's workflow (§V): a volume too large for device memory is
decomposed along Z (``BlockPlan``); blocks are streamed host->device,
computed for ``bt`` temporally-blocked stencil steps, and streamed back
— with each storage unit (remainder / common region) independently
fixed-rate compressed *on device* so only compressed payloads cross the
host<->device boundary, and the common region between contiguous blocks
fetched/written exactly once (the separate-compression dependency fix).

The subsystem is split across three modules:

* ``repro.core.taskgraph`` — the shared representation: every sweep is
  a graph of fetch/decompress/stencil/compress/writeback ``Task``
  objects with dependencies, built by ``build_sweep_tasks`` under a
  pluggable ``Schedule`` (``paper`` / ``unitgrain`` / ``depth-k``).
* ``repro.core.executor`` — the *live* engine: walks the task graph
  asynchronously with a bounded-depth in-flight window that stays open
  across sweep boundaries (2-3 block visits resident, matching the
  paper's three CUDA streams), overlapping H2D, codec+stencil compute,
  and D2H. ``cache_bytes=``/``policy=`` enable the write-back device
  residency manager (``repro.core.unitcache``) that elides resident
  transfers in both directions, and ``checkpoint()``/``restore()``
  snapshot and resume a live run crash-consistently. Bit-identical
  output to the synchronous engine below.
* ``repro.core.pipeline`` — the timeline *replay*: the same graph on an
  event-driven three-stream model with hardware constants (V100/PCIe
  for the paper-faithful Figs. 5/6, TPU host-DMA for the adapted
  projection), pricing the same residency elisions and flush traffic.

This module keeps the synchronous reference engine
(``OutOfCoreWave``, one block at a time, the numerics ground truth the
executor is verified against) and the host-side unit store
(``HostUnitStore``) both engines share. The store distinguishes
committed-on-device from committed-on-host versions (write-back
residency) and serializes itself for checkpoints via ``state_dict`` /
``load_state``; ``docs/architecture.md`` documents the full unit
lifecycle.

Field roles follow paper Table I: two read-write pressure fields, a
write-only Laplacian scratch (never transferred), and a read-only
velocity field (transferred to device, never written back).
"""

from __future__ import annotations

import functools
import os
import zlib
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Literal, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.blocks import BlockPlan
from repro.core.spans import span
from repro.core.taskgraph import Transfer, summarize_transfers
from repro.distributed.fault import (
    ChecksumError,
    FaultInjector,
    InjectedFault,
    RetryPolicy,
    UnrecoverableFault,
)
from repro.kernels.stencil import ops as stencil_ops
from repro.kernels.stencil.ref import HALO
from repro.kernels.zfp import ops as zfp_ops
from repro.kernels.zfp.ref import Compressed

__all__ = [
    "FieldSpec", "OOCConfig", "OutOfCoreWave", "HostUnitStore",
    "Transfer", "paper_code_fields", "unit_shards", "unit_checksum",
    "crc32_combine",
]

Role = Literal["rw", "ro"]


@dataclass(frozen=True)
class FieldSpec:
    role: Role
    planes: Optional[int] = None  # None = uncompressed

    @property
    def compressed(self) -> bool:
        return self.planes is not None


@dataclass
class OOCConfig:
    shape: Tuple[int, int, int]  # interior (Z, Y, X)
    ndiv: int
    bt: int
    fields: Dict[str, FieldSpec]
    backend: str = "ref"  # stencil+codec backend ("ref" | "pallas")
    dtype: str = "float32"

    @property
    def plan(self) -> BlockPlan:
        return BlockPlan(self.shape[0], self.ndiv, self.bt)

    def temporal_plan(self, temporal: int = 1) -> BlockPlan:
        """The block plan a ``temporal-k`` schedule runs against:
        fusing ``k`` sweeps per block visit widens the halo to
        ``radius * bt * k`` planes per side (same unit cover of
        [0, Z), wider common regions).

        Validates the widened footprint with a clear error instead of
        the bare assertions deeper in ``BlockPlan``: the halo width
        must fit the block interior, or remainders/commons would be
        empty or overlapping.
        """
        if temporal < 1:
            raise ValueError(
                f"temporal fusion must be >= 1 sweeps, got {temporal}"
            )
        if self.shape[0] % self.ndiv:
            raise ValueError(
                f"Z={self.shape[0]} must divide into ndiv={self.ndiv} "
                "equal blocks"
            )
        block = self.shape[0] // self.ndiv
        halo = HALO * self.bt * temporal
        # ndiv >= 3 has interior remainders [s+H, e-H), empty at
        # block == 2H; ndiv <= 2 only needs the fetched extent valid
        if 2 * halo > block or (self.ndiv >= 3 and 2 * halo >= block):
            raise ValueError(
                f"halo-width {halo} (= radius {HALO} x bt {self.bt} x "
                f"temporal {temporal}) exceeds the block interior: "
                f"block={block} planes (Z={self.shape[0]}, "
                f"ndiv={self.ndiv}) needs block "
                f"{'>' if self.ndiv >= 3 else '>='} 2*halo={2 * halo}. "
                "Lower the temporal fusion k, bt, or ndiv."
            )
        return BlockPlan(self.shape[0], self.ndiv, self.bt * temporal)

    def to_dict(self) -> Dict[str, object]:
        """JSON-able description (checkpoint manifests); inverse of
        ``from_dict`` — round-trips every field exactly."""
        return {
            "shape": list(self.shape),
            "ndiv": self.ndiv,
            "bt": self.bt,
            "fields": {
                name: {"role": spec.role, "planes": spec.planes}
                for name, spec in self.fields.items()
            },
            "backend": self.backend,
            "dtype": self.dtype,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, object]) -> "OOCConfig":
        return cls(
            shape=tuple(d["shape"]),
            ndiv=int(d["ndiv"]),
            bt=int(d["bt"]),
            fields={
                name: FieldSpec(
                    f["role"],
                    None if f["planes"] is None else int(f["planes"]),
                )
                for name, f in d["fields"].items()
            },
            backend=d.get("backend", "ref"),
            dtype=d.get("dtype", "float32"),
        )


def paper_code_fields(code: int, f32: bool = True) -> Dict[str, FieldSpec]:
    """The four experiment codes of §VI. Rates are the f32-native
    equivalents of the paper's f64 32/64 and 24/64 (same ratios)."""
    r2, r267 = (16, 12) if f32 else (32, 24)
    none = FieldSpec("rw", None)
    if code == 1:  # original (no compression)
        return {
            "p_prev": none, "p_cur": none, "vel2": FieldSpec("ro", None)
        }
    if code == 2:  # one RW dataset @ 2:1
        return {
            "p_prev": FieldSpec("rw", r2), "p_cur": none,
            "vel2": FieldSpec("ro", None),
        }
    if code == 3:  # RO dataset @ 2:1
        return {
            "p_prev": none, "p_cur": none, "vel2": FieldSpec("ro", r2)
        }
    if code == 4:  # one RW + RO @ 2.67:1
        return {
            "p_prev": FieldSpec("rw", r267), "p_cur": none,
            "vel2": FieldSpec("ro", r267),
        }
    raise ValueError(code)


# crc32 over large buffers: one zlib call up to DIGEST_SPLIT bytes;
# above it, DIGEST_CHUNK-byte chunks digested on a thread pool (zlib
# releases the GIL) and joined in order by ``crc32_combine``. The value
# is the one zlib call's either way.
DIGEST_CHUNK = 32 << 20
DIGEST_SPLIT = 64 << 20
_CRC32_POLY = 0xEDB88320  # crc32's generator polynomial, bit-reflected


def _gf2_mul(a: int, b: int) -> int:
    """``a * b`` modulo crc32's polynomial (zlib's ``multmodp``; both
    bit-reflected, x^0 in the top bit)."""
    p = 0
    for bit in range(31, -1, -1):
        if a >> bit & 1:
            p ^= b
        b = (b >> 1) ^ _CRC32_POLY if b & 1 else b >> 1
    return p


@functools.lru_cache(maxsize=64)
def _crc32_shift(nbytes: int) -> int:
    """x^(8 * nbytes) modulo the polynomial: the GF(2) operator that
    carries a crc past ``nbytes`` further bytes (zlib's ``x2nmodp``)."""
    op, power, n = 1 << 31, 1 << 30, 8 * nbytes  # x^0, x^1
    while n:
        if n & 1:
            op = _gf2_mul(power, op)
        power = _gf2_mul(power, power)
        n >>= 1
    return op


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """``zlib.crc32(a + b)`` from ``crc1 = zlib.crc32(a)``, ``crc2 =
    zlib.crc32(b)`` and ``len2 = len(b)`` (zlib's ``crc32_combine``)."""
    return _gf2_mul(_crc32_shift(len2), crc1) ^ crc2


@functools.lru_cache(maxsize=None)
def _digest_pool() -> ThreadPoolExecutor:
    return ThreadPoolExecutor(min(os.cpu_count() or 1, 8),
                              thread_name_prefix="crc32")


def _crc32(buf: np.ndarray, crc: int) -> int:
    """``zlib.crc32`` of the C-contiguous ``buf``'s bytes, continuing
    ``crc``, read in place (no copy)."""
    flat = buf.reshape(-1).view(np.uint8)
    if flat.size <= DIGEST_SPLIT:
        return zlib.crc32(flat, crc)
    chunks = [flat[i:i + DIGEST_CHUNK]
              for i in range(0, flat.size, DIGEST_CHUNK)]
    for chunk, got in zip(chunks, _digest_pool().map(zlib.crc32, chunks)):
        crc = crc32_combine(crc, got, chunk.size)
    return crc


def _digest_parts(value) -> tuple:
    """What ``unit_checksum`` digests of ``value``, in order."""
    if isinstance(value, Compressed):
        return value.payload, value.emax
    return (value,)


def start_host_copy(value) -> None:
    """Start the device-to-host copy of a device payload (a raw array,
    or both leaves of a ``Compressed``) without waiting for it. JAX
    keeps the landed copy on the array, so a later ``np.asarray``
    returns it and waits only for what has not landed yet."""
    leaves = _digest_parts(value)
    with span(spans.D2H_START, bytes=sum(int(x.nbytes) for x in leaves)):
        for leaf in leaves:
            leaf.copy_to_host_async()


def unit_checksum(value, version: int) -> int:
    """crc32 integrity digest of one unit: payload (+emax for
    compressed units) chained with the version it realizes, so a stale
    payload can never pass as a newer one. Computed from *host* bytes
    (for device values ``np.asarray`` is the materialization, and may
    return a copy that ``start_host_copy`` already landed — callers
    on hot paths pass the already-materialized copy), read in place;
    a part over ``DIGEST_SPLIT`` bytes is digested in chunks on
    several threads, to the same value."""
    parts = [np.ascontiguousarray(np.asarray(p))
             for p in _digest_parts(value)]
    with span(spans.CHECKSUM, bytes=sum(p.nbytes for p in parts)):
        crc = zlib.crc32(str(int(version)).encode())
        for p in parts:
            crc = _crc32(p, crc)
    return crc & 0xFFFFFFFF


def unit_shards(
    field: str, kind: str, idx: int, value, version: int,
) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
    """Checkpoint serialization of ONE unit: ``(leaves, meta)``.

    ``leaves`` is the flat shard dict (one array per raw unit, two —
    payload + emax — per compressed unit, keyed ``field.kindidx[...]``)
    and ``meta`` the JSON-able descriptor carrying the codec and the
    version the payload realizes. Shared by ``HostUnitStore.
    state_dict`` (the quiesced snapshot of the whole store) and the
    executor's overlapped checkpoint (which persists units one at a
    time, from pinned device payloads, while the next sweep runs).
    ``value`` may be a host or device payload; leaves are materialized
    to host numpy arrays here (for device values this is the D2H).
    """
    ukey = f"{field}.{kind}{idx}"
    meta: Dict[str, object] = {
        "field": field, "kind": kind, "idx": idx, "version": int(version),
    }
    leaves: Dict[str, np.ndarray] = {}
    if isinstance(value, Compressed):
        payload, emax = np.asarray(value.payload), np.asarray(value.emax)
        leaves[f"{ukey}.payload"] = payload
        leaves[f"{ukey}.emax"] = emax
        meta.update(
            codec="zfp", shape=list(value.shape),
            planes=value.planes,
            ndim_spatial=value.ndim_spatial,
            dtype=str(value.dtype),
        )
        host: object = Compressed(
            payload, emax, value.shape, value.planes,
            value.ndim_spatial, value.dtype,
        )
    else:
        host = leaves[ukey] = np.asarray(value)
        meta["codec"] = "raw"
    # integrity digest of the persisted bytes: verified by
    # HostUnitStore.load_state on restore, before any payload is
    # consumed (the manifest additionally digests the shard files
    # themselves — this one pins payload<->version)
    meta["crc32"] = unit_checksum(host, version)
    return leaves, meta


class HostUnitStore:
    """Host-side storage of units, raw (numpy) or compressed payloads.

    Shared by the synchronous engine and the async executor: seeding,
    unit put/get, host->device staging, and full-field gather all live
    here so both engines see byte-identical host state.
    """

    def __init__(
        self,
        cfg: OOCConfig,
        plan: Optional[BlockPlan] = None,
        *,
        injector: Optional[FaultInjector] = None,
        retry: Optional[RetryPolicy] = None,
        stats=None,
        rates=None,
    ):
        self.cfg = cfg
        # optional RateController: when attached, ``seed`` encodes each
        # unit at its per-unit sweep-0 rate instead of the field spec's
        # (rate None = store raw / lossless)
        self.rates = rates
        # the unit layout this store is decomposed under — a temporal-k
        # engine passes its halo-widened plan (same cover, wider
        # commons); default is the config's base plan
        self.plan = plan if plan is not None else cfg.plan
        self._units: Dict[Tuple[str, str, int], object] = {}
        # writebacks since seeding, per unit (seeded units are v0) —
        # the executor's fetch-after-writeback hazard tracking and the
        # device unit cache both key validity on these counters. Under
        # the write-back residency policy a version can be *committed
        # on device* without a host copy: ``_versions`` then runs ahead
        # of ``_host_versions`` until a flush ``put``s the payload.
        self._versions: Dict[Tuple[str, str, int], int] = {}
        self._host_versions: Dict[Tuple[str, str, int], int] = {}
        # integrity digests of the committed host payloads (crc32 over
        # payload+emax+version, ``unit_checksum``): recorded at every
        # put, verified at every fetch (h2d), on every corrupted d2h
        # copy and on restore — a corrupted unit is caught before any
        # stencil step can consume it
        self._crc: Dict[Tuple[str, str, int], int] = {}
        # the self-healing hooks: ``injector`` replays a FaultPlan on
        # every crossing, ``retry`` bounds the attempts, ``stats`` is
        # an optional CacheStats mirror for the executor's counters
        self.injector = injector
        self.retry = retry
        self.stats = stats
        # one (op, field, unit, version, attempts) record per
        # completed crossing — the live side of the model/live
        # attempt-multiset parity contract
        self.wire_log: List[Tuple[str, str, str, int, int]] = []
        self.wire_stats: Dict[str, int] = {
            "h2d_retries": 0, "d2h_retries": 0, "wire_faults": 0,
            "checksum_failures": 0, "wire_stragglers": 0,
            # bytes read by the digests of puts, crossings and
            # restores (``_digest``)
            "digest_bytes": 0,
            # puts whose value's host copy was started before the put
            # was reached (``start_host_copy``)
            "d2h_early": 0,
        }
        self.backoff_s = 0.0  # accounted backoff time (never slept)

    # ------------------------------------------------------------------
    # the integrity-checked wire
    # ------------------------------------------------------------------
    def _count(self, name: str) -> None:
        self.wire_stats[name] += 1
        if self.stats is not None:
            setattr(self.stats, name, getattr(self.stats, name) + 1)

    def _digest(self, value, version: int) -> int:
        """``unit_checksum``, its bytes counted in ``digest_bytes``."""
        self.wire_stats["digest_bytes"] += sum(
            int(p.nbytes) for p in _digest_parts(value))
        return unit_checksum(value, version)

    def _wire(self, op: str, field: str, kind: str, idx: int,
              version: int, host, crc: int, *,
              host_digested: bool = False):
        """One integrity-checked link crossing under the retry policy.

        ``host`` is the already-materialized host-side value and
        ``crc`` the checksum it must realize. Each attempt consults the
        injector (transfer failure / in-flight bit-flip), then
        verifies the received bytes against ``crc`` — corruption is
        *always* detected here, before the payload can be stored or
        shipped to a stencil step. ``host_digested`` says ``crc`` was
        just computed from ``host`` itself (a put): a received copy
        that *is* ``host`` then holds exactly the digested bytes and
        is accepted without a second digest, and only a copy the
        injector made (``corrupt``) is digested. Otherwise (a stage,
        whose ``crc`` is the one recorded at the put) every attempt
        re-digests the stored bytes, which catches tampering at rest.
        Failed attempts retry up to ``retry.attempts`` with accounted
        (never slept) exponential backoff; exhaustion raises
        ``UnrecoverableFault`` chaining the last failure. Returns the
        verified value.
        """
        unit = f"{kind}{idx}"
        attempts = self.retry.attempts if self.retry else 1
        last: Optional[Exception] = None
        for attempt in range(attempts):
            if attempt:
                self._count(f"{op}_retries")
                if self.retry is not None:
                    self.backoff_s += self.retry.backoff(attempt)
            fault = None
            if self.injector is not None:
                fault = self.injector.transfer_fault(
                    op, field, unit, version, attempt
                )
            if fault == "transfer":
                self._count("wire_faults")
                last = InjectedFault(
                    f"injected {op} failure: {field}.{unit} "
                    f"v{version} attempt {attempt}"
                )
                continue
            received = host
            if fault == "corrupt":
                self._count("wire_faults")
                if isinstance(host, Compressed):
                    received = Compressed(
                        FaultInjector.corrupt(host.payload), host.emax,
                        host.shape, host.planes, host.ndim_spatial,
                        host.dtype,
                    )
                else:
                    received = FaultInjector.corrupt(host)
            if received is not host or not host_digested:
                got = self._digest(received, version)
                if got != crc:
                    self._count("checksum_failures")
                    last = ChecksumError(
                        f"{op} checksum mismatch for unit {field}.{unit} "
                        f"v{version}: expected {crc:#010x}, got {got:#010x}"
                    )
                    continue
            if self.injector is not None and self.injector.straggle(
                op, field, unit, version
            ) > 1.0:
                self._count("wire_stragglers")
            self.wire_log.append((op, field, unit, int(version),
                                  attempt + 1))
            return received
        raise UnrecoverableFault(
            f"{op} of unit {field}.{unit} v{version} failed after "
            f"{attempts} attempt(s): {last}"
        ) from last

    def attempt_multiset(self) -> Counter:
        """Multiset of completed crossings with their attempt counts —
        compare against ``Timeline.attempt_multiset()`` under the same
        ``FaultPlan`` for model/live parity."""
        return Counter(self.wire_log)

    def put(
        self, field: str, kind: str, idx: int, value,
        version: Optional[int] = None,
        on_wire: bool = True,
        op: str = "d2h",
        early: bool = False,
    ) -> int:
        """Store; returns wire bytes (what crossed the link).

        ``version`` pins the committed version this payload realizes
        (deferred writebacks and residency flushes); without it the
        counter bumps by one (the synchronous engine's in-order path).
        Either way the host copy is current afterwards. The D2H
        crossing is integrity-checked: the checksum is computed once,
        from the source bytes, and a received copy that differs from
        them (injected corruption) must match it (corruption and
        transfer failures retry under the store's ``RetryPolicy``).
        ``on_wire=False`` marks a host-local put (seeding) that never
        crosses the link — exempt from injection, but still digested.
        ``op`` labels the crossing in the wire log (and for fault
        injection): ``"d2h"`` for the device->host link, ``"halo"``
        for an inter-device halo put landing in a neighbor shard's
        ghost mirror.

        The put waits for the value, materializes it (``np.asarray``),
        digests it and crosses the wire. ``early`` says that the caller
        started the value's host copy before the put
        (``start_host_copy``): ``np.asarray`` then returns that copy,
        already landed or landing, and waits only for the part still in
        flight; such a put counts in ``wire_stats["d2h_early"]`` and its
        span says ``early``.
        """
        key = (field, kind, idx)
        if version is None:
            version = self._versions.get(key, -1) + 1
        assert version >= self._host_versions.get(key, 0), key
        compressed = isinstance(value, Compressed)
        wire = int(value.nbytes() if compressed else value.nbytes)
        with span(spans.PUT, field=field, unit=f"{kind}{idx}", bytes=wire,
                  op=op, early=early):
            with span(spans.WAIT):
                jax.block_until_ready(value)
            # materialize once — for device values this is the D2H, or
            # the collection of a copy started at park
            with span(spans.D2H):
                if compressed:
                    host: object = Compressed(
                        np.asarray(value.payload), np.asarray(value.emax),
                        value.shape, value.planes, value.ndim_spatial,
                        value.dtype,
                    )
                else:
                    host = np.asarray(value)
            crc = self._digest(host, version)
            if on_wire:
                host = self._wire(op, field, kind, idx, version, host, crc,
                                  host_digested=True)
            # store the payload BEFORE advancing the version maps: a put
            # that fails mid-copy must not leave host_current() true over
            # stale bytes (the flush-retry contract relies on this order).
            # The replaced payload is freed here, inside the span.
            self._units[key] = host
            self._crc[key] = crc
            self._versions[key] = max(version, self._versions.get(key, 0))
            self._host_versions[key] = version
        self.wire_stats["d2h_early"] += early
        return wire

    def get(self, field: str, kind: str, idx: int):
        # a stale host payload must never be served: under write-back
        # the committed version lives on device until flushed, so every
        # host read path (stage, gather, checkpointing) has to flush
        # first — this guard makes forgetting that loud, for raw units
        # (which skip stage()) as much as compressed ones
        assert self.host_current(field, kind, idx), (field, kind, idx)
        return self._units[(field, kind, idx)]

    def version_of(self, field: str, kind: str, idx: int) -> int:
        """Committed writebacks since seeding (0 = still the seed).
        Counts device-only commits too — see ``host_current``."""
        return self._versions.get((field, kind, idx), 0)

    def host_version_of(self, field: str, kind: str, idx: int) -> int:
        """Version of the payload actually held on host."""
        return self._host_versions.get((field, kind, idx), 0)

    def host_current(self, field: str, kind: str, idx: int) -> bool:
        """Whether the host payload realizes the committed version.
        False only under write-back residency, between a device-side
        version commit and its flush."""
        key = (field, kind, idx)
        return (
            self._host_versions.get(key, 0) == self._versions.get(key, 0)
        )

    def unit_keys(self) -> List[Tuple[str, str, int]]:
        """All stored unit keys, sorted — the deterministic iteration
        order snapshots use."""
        return sorted(self._units)

    def host_payload(self, field: str, kind: str, idx: int,
                     min_version: int):
        """The raw host payload object for a snapshot capture.

        Unlike ``get`` (which demands full ``host_current`` — the
        committed version), this serves a *frozen-cut* read: the
        caller needs the payload realizing at least ``min_version``
        (its cut version), which may be older than a later committed
        one. Asserts the host copy is new enough, so a stale capture
        still fails loudly. Returned objects are never mutated by the
        store (puts replace them), so holding the reference across
        later puts is safe.
        """
        assert self.host_version_of(field, kind, idx) >= min_version, (
            "snapshot capture of a stale host payload",
            field, kind, idx, min_version,
        )
        return self._units[(field, kind, idx)]

    def commit_device(
        self, field: str, kind: str, idx: int, version: int
    ) -> None:
        """Commit ``version`` with the payload resident on device only
        (the write-back elision): no host copy is made, so the host
        entry is stale until a flush ``put``s it. The caller (the
        executor's drain) guarantees the payload stays resident dirty
        until then."""
        key = (field, kind, idx)
        assert version > self._versions.get(key, 0), key
        self._versions[key] = version

    # ------------------------------------------------------------------
    # checkpoint serialization
    # ------------------------------------------------------------------
    def state_dict(self) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
        """Serializable snapshot: ``(leaves, meta)``.

        ``leaves`` is a flat dict of host numpy arrays (one checkpoint
        shard per raw unit, two — payload + emax — per compressed
        unit); ``meta`` is the JSON-able per-unit table carrying codec
        descriptors and the committed version vector. The snapshot is
        only taken at a consistent cut: every unit must be
        ``host_current`` (i.e. all dirty residency flushed first —
        ``AsyncExecutor.checkpoint`` guarantees this), asserted here so
        a checkpoint can never capture stale host bytes.
        """
        leaves: Dict[str, np.ndarray] = {}
        units: Dict[str, Dict[str, object]] = {}
        for (field, kind, idx), stored in sorted(self._units.items()):
            assert self.host_current(field, kind, idx), (
                "checkpoint of a stale host unit — flush residency "
                "before snapshotting", field, kind, idx,
            )
            uleaves, meta = unit_shards(
                field, kind, idx, stored,
                self._versions.get((field, kind, idx), 0),
            )
            leaves.update(uleaves)
            units[f"{field}.{kind}{idx}"] = meta
        return leaves, {"units": units}

    def load_state(
        self,
        leaves: Dict[str, np.ndarray],
        meta: Dict[str, object],
    ) -> None:
        """Rebuild the store from a ``state_dict`` snapshot: payloads,
        compressed-unit handles, and the version vector (host ==
        committed at a checkpoint cut, so both maps restore equal).

        Restore is a verification point: every unit carrying a
        recorded ``crc32`` is re-digested and must match — a snapshot
        tampered with (or bit-rotted) after ``read_manifest``'s
        shard-level digests is still refused here, naming the unit,
        before any payload can seed a resumed run.
        """
        self._units.clear()
        self._versions.clear()
        self._host_versions.clear()
        self._crc.clear()
        for ukey, u in meta["units"].items():
            key = (u["field"], u["kind"], int(u["idx"]))
            if u["codec"] == "zfp":
                value: object = Compressed(
                    np.ascontiguousarray(leaves[f"{ukey}.payload"]),
                    np.ascontiguousarray(leaves[f"{ukey}.emax"]),
                    tuple(u["shape"]), int(u["planes"]),
                    int(u["ndim_spatial"]), u["dtype"],
                )
            else:
                value = np.ascontiguousarray(leaves[ukey])
            ver = int(u["version"])
            crc = self._digest(value, ver)
            want = u.get("crc32")  # pre-PR 7 snapshots carry none
            if want is not None and int(want) != crc:
                raise ChecksumError(
                    f"restore refused: unit {ukey} v{ver} does not "
                    f"match its recorded digest (expected "
                    f"{int(want):#010x}, got {crc:#010x}) — the "
                    "snapshot shard is corrupt; restore from an "
                    "earlier step_<k> directory"
                )
            self._units[key] = value
            self._crc[key] = crc
            self._versions[key] = ver
            self._host_versions[key] = ver

    def seed(
        self,
        full: Dict[str, np.ndarray],
        keys: Optional[Sequence[Tuple[str, int]]] = None,
    ) -> None:
        """Initial decomposition of full fields into host units.
        (In production this is the I/O layer; unit-wise so the full
        volume never has to exist on the device.)

        ``keys`` restricts seeding to the given ``(kind, idx)`` units —
        a shard's local footprint. Compression is per-unit and
        deterministic, so a subset seed holds bit-identical payloads to
        the same units of a full seed.
        """
        cfg = self.cfg
        plan = self.plan
        keep = None if keys is None else set(keys)
        for name, arr in full.items():
            spec = cfg.fields[name]
            assert arr.shape == cfg.shape
            units = [(kind, idx, jnp.asarray(arr[lo:hi]))
                     for kind, idx, (lo, hi) in plan.units()
                     if keep is None or (kind, idx) in keep]
            if spec.compressed:
                if self.rates is not None:
                    # per-unit sweep-0 rates (None entries pass through
                    # raw = lossless)
                    per_unit = [
                        self.rates.rate_for(name, k, i, 0)
                        for k, i, _ in units
                    ]
                else:
                    per_unit = spec.planes
                comp = zfp_ops.compress_units(
                    [u for _, _, u in units], planes=per_unit, ndim=3,
                    backend=cfg.backend,
                )
                units = [(k, i, c) for (k, i, _), c in zip(units, comp)]
            for kind, idx, unit in units:
                # seeding is host-local decomposition, not a link
                # crossing — exempt from fault injection (and from the
                # wire log the parity tests compare)
                self.put(name, kind, idx, unit, on_wire=False)

    def stage(self, field: str, kind: str, idx: int):
        """Host -> device for one unit WITHOUT decompressing.

        Returns ``(device_value, raw_bytes, wire_bytes)`` where
        ``device_value`` is a device array or an on-device
        ``Compressed`` awaiting a decompress task. The H2D crossing is
        integrity-checked against the checksum recorded when the unit
        was committed: a tampered host payload or in-flight corruption
        raises before the bytes can reach a decompress/stencil task.
        """
        # a stale host copy must never cross the link: write-back
        # keeps the invariant "committed-ahead-of-host implies
        # dirty-resident", so every real fetch sees current bytes
        assert self.host_current(field, kind, idx), (field, kind, idx)
        key = (field, kind, idx)
        stored = self.get(field, kind, idx)
        version = self._host_versions.get(key, 0)
        compressed = isinstance(stored, Compressed)
        wire = int(stored.nbytes() if compressed else stored.nbytes)
        with span(spans.STAGE, field=field, unit=f"{kind}{idx}", bytes=wire):
            crc = self._crc.get(key)
            if crc is None:  # pre-digest stores (legacy direct loads)
                crc = self._crc[key] = self._digest(stored, version)
            stored = self._wire(
                "h2d", field, kind, idx, version, stored, crc
            )
            with span(spans.H2D):
                if compressed:
                    dev = Compressed(
                        jnp.asarray(stored.payload), jnp.asarray(stored.emax),
                        stored.shape, stored.planes, stored.ndim_spatial,
                        stored.dtype,
                    )
                else:
                    dev = jnp.asarray(stored)
        if compressed:
            raw = int(np.prod(stored.shape)) * np.dtype(stored.dtype).itemsize
            return dev, raw, wire
        return dev, wire, wire

    def checksum_of(self, field: str, kind: str, idx: int) -> int:
        """The recorded integrity digest of the committed host
        payload (tests and the checkpoint writer read it)."""
        return self._crc[(field, kind, idx)]

    def gather(self, name: str) -> np.ndarray:
        """Reassemble a full field from host units (decompressing).

        Compressed units are staged and decoded through the batched
        ``decompress_units`` entry point: every unit's decoder is
        dispatched before any payload is awaited, instead of one
        synchronous stage/decode round-trip per unit.
        """
        cfg = self.cfg
        out = np.zeros(cfg.shape, dtype=cfg.dtype)
        comp_spans: List[Tuple[int, int]] = []
        comp_payloads: List[Compressed] = []
        for kind, idx, (lo, hi) in self.plan.units():
            stored = self.get(name, kind, idx)
            if isinstance(stored, Compressed):
                dev, _, _ = self.stage(name, kind, idx)
                comp_spans.append((lo, hi))
                comp_payloads.append(dev)
            else:
                out[lo:hi] = stored
        if comp_payloads:
            decoded = zfp_ops.decompress_units(
                comp_payloads, backend=cfg.backend
            )
            for (lo, hi), arr in zip(comp_spans, decoded):
                out[lo:hi] = np.asarray(arr)
        return out


class OutOfCoreWave:
    """The paper's out-of-core acoustic propagator (synchronous).

    One block visit at a time: fetch, decompress, compute, compress,
    write back, then the next block. This is the numerics ground truth;
    ``repro.core.executor.AsyncExecutor`` runs the same ops overlapped
    and must stay bit-identical to it.

    ``temporal=k`` runs the engine as the temporal-k ground truth:
    every visit fetches the halo-k widened footprint, advances the
    fused ``bt*k`` steps on device, and writes each unit back once
    with ``k`` version bumps (one codec round-trip per *round*, not
    per sweep — temporal blocking reduces lossy re-encodes too).
    """

    def __init__(
        self,
        cfg: OOCConfig,
        p_prev: np.ndarray,
        p_cur: np.ndarray,
        vel2: np.ndarray,
        temporal: int = 1,
        rates=None,
    ):
        self.cfg = cfg
        self.temporal = temporal
        self.plan = cfg.temporal_plan(temporal)
        self.plan.check_cover()
        # optional RateController: per-unit encode rates (adaptive or
        # pinned-lossless); None keeps the fixed spec-rate paths
        self.rates = rates
        self.store = HostUnitStore(cfg, plan=self.plan, rates=rates)
        self.transfers: List[Transfer] = []
        self.sweeps_done = 0
        self.store.seed({"p_prev": p_prev, "p_cur": p_cur, "vel2": vel2})

    # ------------------------------------------------------------------
    def _fetch_unit(self, name: str, kind: str, idx: int, sweep: int,
                    block: int) -> jax.Array:
        """Host -> device for one unit, decompressing on device."""
        dev, raw, wire = self.store.stage(name, kind, idx)
        self.transfers.append(Transfer(
            "h2d", name, (kind, idx), raw, wire, sweep, block
        ))
        if isinstance(dev, Compressed):
            return zfp_ops.decompress(dev, backend=self.cfg.backend)
        return dev

    def _write_unit(self, name: str, kind: str, idx: int, value: jax.Array,
                    sweep: int, block: int, bump: int = 1) -> None:
        """Device -> host for one unit, compressing on device.
        ``bump`` is the number of sweeps this single writeback commits
        (= the round's fused sweep count under temporal-k)."""
        spec = self.cfg.fields[name]
        raw = int(value.size) * value.dtype.itemsize
        ver = self.store.version_of(name, kind, idx) + bump
        if self.rates is not None:
            planes = self.rates.rate_for(name, kind, idx, sweep)
        else:
            planes = spec.planes if spec.compressed else None
        if planes is not None:
            comp = zfp_ops.compress(
                value, planes=planes, ndim=3, backend=self.cfg.backend
            )
            if self.rates is not None and spec.compressed:
                q = zfp_ops.quantize(value, planes=planes, ndim=3)
                self.rates.observe(
                    name, kind, idx, planes,
                    float(jnp.max(jnp.abs(q - value))),
                    float(jnp.max(jnp.abs(value))),
                )
            wire = self.store.put(name, kind, idx, comp, version=ver)
        else:
            if self.rates is not None and spec.compressed:
                # lossless commit: zero error at the unit's amplitude
                self.rates.observe(
                    name, kind, idx, None, 0.0,
                    float(jnp.max(jnp.abs(value))),
                )
            wire = self.store.put(name, kind, idx, value, version=ver)
        self.transfers.append(
            Transfer("d2h", name, (kind, idx), raw, wire, sweep, block)
        )

    # ------------------------------------------------------------------
    def _assemble(
        self, name: str, i: int, shared: Optional[jax.Array], sweep: int
    ) -> jax.Array:
        """Build the fetched (B+2H, Y, X) device field for block i."""
        plan = self.plan
        h, b = plan.halo, plan.block
        _, y, x = self.cfg.shape
        zeros = lambda n: jnp.zeros((n, y, x), dtype=jnp.dtype(self.cfg.dtype))
        pieces = []
        if i == 0:
            pieces.append(zeros(h))
        else:
            if shared is not None:
                pieces.append(shared)  # C_{i-1} already on device
            else:
                pieces.append(self._fetch_unit(name, "C", i - 1, sweep, i))
        pieces.append(self._fetch_unit(name, "R", i, sweep, i))
        if i < plan.ndiv - 1:
            pieces.append(self._fetch_unit(name, "C", i, sweep, i))
        else:
            pieces.append(zeros(h))
        out = jnp.concatenate(pieces, axis=0)
        assert out.shape[0] == b + 2 * h, out.shape
        return out

    # ------------------------------------------------------------------
    def sweep(self, sweeps: Optional[int] = None) -> None:
        """One pass over all blocks; advances the volume by
        ``bt * sweeps`` steps (``sweeps`` defaults to the engine's
        temporal fusion and may be smaller on a truncated final
        round — never larger, the halo only covers ``temporal``)."""
        cfg, plan = self.cfg, self.plan
        kr = self.temporal if sweeps is None else sweeps
        assert 1 <= kr <= self.temporal, (kr, self.temporal)
        h, b = plan.halo, plan.block
        sweep_no = self.sweeps_done
        held: Dict[str, jax.Array] = {}  # lower half of C_{i-1} at t+bt
        shared: Dict[str, Optional[jax.Array]] = {
            n: None for n in cfg.fields
        }
        for i in range(plan.ndiv):
            dev: Dict[str, jax.Array] = {}
            new_shared: Dict[str, jax.Array] = {}
            for name in cfg.fields:
                arr = self._assemble(name, i, shared[name], sweep_no)
                if i < plan.ndiv - 1:
                    # keep the time-t common region for block i+1
                    new_shared[name] = arr[b : b + 2 * h]
                dev[name] = arr
            pp, pc = stencil_ops.fused_temporal_steps(
                dev["p_prev"], dev["p_cur"], dev["vel2"],
                steps=cfg.bt * kr, backend=cfg.backend,
            )
            s, _ = plan.owned(i)
            for name, new in (("p_prev", pp), ("p_cur", pc)):
                owned = new[h : h + b]
                rlo, rhi = plan.remainder(i)
                self._write_unit(
                    name, "R", i, owned[rlo - s : rhi - s], sweep_no, i,
                    bump=kr,
                )
                if i > 0:
                    cm = jnp.concatenate([held[name + str(i - 1)], owned[:h]])
                    self._write_unit(
                        name, "C", i - 1, cm, sweep_no, i, bump=kr
                    )
                if i < plan.ndiv - 1:
                    held[name + str(i)] = owned[b - h : b]
            shared = {n: new_shared.get(n) for n in cfg.fields}
        self.sweeps_done += kr
        if self.rates is not None:
            # sweep boundary: re-decide the rate map from this round's
            # observations; the new map applies from the next sweep on
            self.rates.decide(self.sweeps_done)

    def run(self, total_steps: int) -> None:
        assert total_steps % self.cfg.bt == 0
        remaining = total_steps // self.cfg.bt
        while remaining:
            kr = min(self.temporal, remaining)
            self.sweep(kr)
            remaining -= kr

    def finish(self) -> None:
        """API parity with ``AsyncExecutor``: the synchronous engine
        writes back within each sweep, so there is nothing to drain."""

    # ------------------------------------------------------------------
    def gather(self, name: str) -> np.ndarray:
        return self.store.gather(name)

    # ------------------------------------------------------------------
    def transfer_summary(self) -> Dict[str, int]:
        return summarize_transfers(self.transfers)
