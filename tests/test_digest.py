"""The store's integrity digest (``unit_checksum``) and its cost.

The digest reads each payload in place and, above ``DIGEST_SPLIT``
bytes, in ``DIGEST_CHUNK``-byte chunks on several threads joined by
``crc32_combine``. Its value must stay the one of the plain chain
``zlib.crc32(str(version))`` -> payload bytes -> emax bytes that every
recorded digest and every checkpoint on disk holds. The store digests
each crossing's bytes once, and counts what it reads in
``wire_stats["digest_bytes"]``.
"""

import sys
import zlib
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import outofcore
from repro.core.executor import AsyncExecutor
from repro.core.outofcore import (
    DIGEST_CHUNK,
    DIGEST_SPLIT,
    HostUnitStore,
    OOCConfig,
    crc32_combine,
    paper_code_fields,
    unit_checksum,
)
from repro.distributed.fault import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
    RetryPolicy,
)
from repro.kernels.stencil import ref as stencil_ref
from repro.kernels.zfp.ref import Compressed

SHAPE = (32, 8, 8)


def _chain(value, version: int) -> int:
    """The digest as first defined: every part copied out with
    ``tobytes`` and chained through one ``zlib.crc32`` call each."""
    parts = ((value.payload, value.emax) if isinstance(value, Compressed)
             else (value,))
    crc = zlib.crc32(str(int(version)).encode())
    for p in parts:
        crc = zlib.crc32(np.ascontiguousarray(np.asarray(p)).tobytes(), crc)
    return crc & 0xFFFFFFFF


def _bytes(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def _compressed(nb: int, seed: int = 0) -> Compressed:
    rng = np.random.default_rng(seed)
    payload = rng.integers(0, 2**32, (12, nb), dtype=np.uint32)
    emax = rng.integers(-130, 130, nb, dtype=np.int32)
    return Compressed(payload, emax, (4, 4, 4 * nb), 12, 3, "float32")


# ----------------------------------------------------------------------
# the value: bit-identical to the chain
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n", [
    0, 1, 5 * 1024 + 1, DIGEST_SPLIT - 1, DIGEST_SPLIT, DIGEST_SPLIT + 1,
    DIGEST_SPLIT + DIGEST_CHUNK // 2 + 3,
])
def test_raw_digest_is_the_chain_around_the_split(n):
    a = _bytes(n, seed=n)
    assert unit_checksum(a, n % 7) == _chain(a, n % 7)


@pytest.mark.parametrize("version", [0, 1, 9, 10, 123456789])
@pytest.mark.parametrize("dtype", [np.float32, np.uint16, np.int8])
def test_raw_digest_is_the_chain_at_each_version(version, dtype):
    a = np.arange(3 * 5 * 7, dtype=dtype).reshape(3, 5, 7)
    assert unit_checksum(a, version) == _chain(a, version)


@pytest.mark.parametrize("nb", [0, 1, 129, 4096])
def test_compressed_digest_is_the_chain(nb):
    c = _compressed(nb, seed=nb)
    for version in (0, 3, 41):
        assert unit_checksum(c, version) == _chain(c, version)


def test_large_compressed_digest_is_the_chain():
    """A payload over the split goes through the chunks, its emax
    through one call, both chained in order."""
    c = _compressed(DIGEST_SPLIT // 48 + 77)
    assert c.payload.nbytes > DIGEST_SPLIT
    assert unit_checksum(c, 5) == _chain(c, 5)


@pytest.mark.parametrize("view", ["strided", "transposed", "fortran",
                                  "jax", "scalar"])
def test_non_contiguous_and_device_inputs(view):
    base = np.random.default_rng(3).standard_normal((6, 10, 12)).astype(
        np.float32)
    value = {
        "strided": lambda: base[:, ::2, 1::3],
        "transposed": lambda: base.transpose(2, 0, 1),
        "fortran": lambda: np.asfortranarray(base),
        "jax": lambda: jnp.asarray(base),
        "scalar": lambda: np.float32(2.5),
    }[view]()
    assert unit_checksum(value, 4) == _chain(value, 4)


@pytest.mark.parametrize("n", [0, 1, 63, 64, 65, 128, 129, 200, 64 * 7,
                               64 * 7 + 1, 1000])
def test_chunked_path_at_small_chunks(monkeypatch, n):
    """With a 64-byte chunk and a 128-byte split, every size past the
    split runs the threaded path: whole and partial last chunks, raw
    and compressed."""
    monkeypatch.setattr(outofcore, "DIGEST_CHUNK", 64)
    monkeypatch.setattr(outofcore, "DIGEST_SPLIT", 128)
    a = _bytes(n, seed=n + 1)
    assert unit_checksum(a, 2) == _chain(a, 2)
    c = _compressed(n // 8 + 1, seed=n)
    assert unit_checksum(c, 6) == _chain(c, 6)


def test_combine_on_random_splits():
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.settings(max_examples=200, deadline=None,
                         derandomize=True)
    @hypothesis.given(st.binary(max_size=4096), st.data())
    def combine(data, draw):
        cut = draw.draw(st.integers(0, len(data)))
        a, b = data[:cut], data[cut:]
        assert crc32_combine(zlib.crc32(a), zlib.crc32(b), len(b)) == (
            zlib.crc32(data))

    combine()


@pytest.mark.parametrize("len2", [0, 1, 3, 4096, (1 << 20) + 5])
def test_combine_matches_zlib_past_zeros(len2):
    head = zlib.crc32(b"out-of-core")
    assert crc32_combine(head, zlib.crc32(bytes(len2)), len2) == (
        zlib.crc32(b"out-of-core" + bytes(len2)))


@pytest.mark.parametrize("n, m", [(DIGEST_CHUNK, 5), (1 << 34, 1 << 33),
                                  (3, (1 << 40) + 1)])
def test_combine_is_associative_at_any_length(n, m):
    """The operator for ``n + m`` bytes is that for ``n`` then ``m``:
    checked at lengths no test could allocate."""
    a, b, c = 0x1234ABCD, 0x0BADF00D, 0xCAFE0001
    assert crc32_combine(crc32_combine(a, b, n), c, m) == crc32_combine(
        a, crc32_combine(b, c, m), n + m)


def test_checkpoint_digests_are_the_chain_and_restore():
    """A snapshot's recorded digests are the chain's, so checkpoints
    written before and after the chunked digest restore alike."""
    cfg = OOCConfig(SHAPE, 2, 1, paper_code_fields(4))
    p_cur = np.asarray(stencil_ref.ricker_source(SHAPE), dtype=np.float32)
    store = HostUnitStore(cfg)
    store.seed({"p_prev": 0.9 * p_cur, "p_cur": p_cur,
                "vel2": np.full(SHAPE, 0.07, np.float32)})
    leaves, meta = store.state_dict()
    for ukey, u in meta["units"].items():
        if u["codec"] == "zfp":
            value = Compressed(leaves[f"{ukey}.payload"],
                               leaves[f"{ukey}.emax"], tuple(u["shape"]),
                               u["planes"], u["ndim_spatial"], u["dtype"])
        else:
            value = leaves[ukey]
        assert u["crc32"] == _chain(value, u["version"]), ukey
    HostUnitStore(cfg).load_state(leaves, meta)


# ----------------------------------------------------------------------
# the cost: one digest per crossing, counted
# ----------------------------------------------------------------------
def _store(plan=None, attempts=1):
    cfg = OOCConfig(SHAPE, 2, 1, paper_code_fields(4))
    injector = FaultInjector(plan) if plan is not None else None
    return HostUnitStore(cfg, injector=injector,
                         retry=RetryPolicy(attempts=attempts))


@pytest.mark.parametrize("compressed", [False, True])
def test_digest_bytes_one_put_and_one_stage(compressed):
    store = _store()
    value = (_compressed(40) if compressed
             else np.ones((16, 8, 8), np.float32))
    read = (value.payload.nbytes + value.emax.nbytes if compressed
            else value.nbytes)
    wire = store.put("p_prev", "R", 0, value)
    assert wire == (value.nbytes() if compressed else value.nbytes)
    assert store.wire_stats["digest_bytes"] == read
    store.stage("p_prev", "R", 0)
    assert store.wire_stats["digest_bytes"] == 2 * read
    assert store.wire_stats["checksum_failures"] == 0


@pytest.mark.parametrize("compressed", [False, True])
def test_corrupted_put_digests_the_received_copy(compressed):
    """An injected ``corrupt`` hands the put a new object: it is
    digested, refused, and the retry that receives the source bytes
    again is accepted on the source's digest."""
    plan = FaultPlan([FaultSpec(kind="corrupt", op="d2h", field="p_prev",
                                unit="R0", attempts=1)])
    store = _store(plan, attempts=2)
    value = (_compressed(40) if compressed
             else np.ones((16, 8, 8), np.float32))
    read = (value.payload.nbytes + value.emax.nbytes if compressed
            else value.nbytes)
    store.put("p_prev", "R", 0, value)
    assert store.wire_stats["checksum_failures"] == 1
    assert store.wire_stats["d2h_retries"] == 1
    assert store.wire_stats["digest_bytes"] == 2 * read
    version = store.host_version_of("p_prev", "R", 0)
    assert store.checksum_of("p_prev", "R", 0) == _chain(value, version)
    assert store.attempt_multiset() == {
        ("d2h", "p_prev", "R0", version, 2): 1}


def test_engine_digests_each_crossing_once():
    """Streamed with every field raw, a round's digests read exactly
    the bytes that crossed the link, each way once; the executor's
    ``stats()["wire"]`` carries the counter."""
    p_cur = np.asarray(stencil_ref.ricker_source((48, 8, 8)), np.float32)
    cfg = OOCConfig((48, 8, 8), 3, 1, paper_code_fields(1))
    eng = AsyncExecutor(cfg, 0.95 * p_cur, p_cur,
                        np.full((48, 8, 8), 0.07, np.float32),
                        schedule="depth2", cache_bytes=0)
    eng.run(1)
    before, moved0 = eng.stats()["wire"]["digest_bytes"], \
        eng.transfer_summary()
    eng.run(1)
    eng.finish()
    moved = eng.transfer_summary()
    crossed = sum(moved[k] - moved0.get(k, 0)
                  for k in ("h2d_wire", "d2h_wire"))
    assert crossed > 0
    assert eng.stats()["wire"]["digest_bytes"] - before == crossed


def test_concurrent_digests_share_the_pool(monkeypatch):
    """Callers on many threads at once, each with parts chunked over the
    one pool, all get the chain's value."""
    monkeypatch.setattr(outofcore, "DIGEST_CHUNK", 64)
    monkeypatch.setattr(outofcore, "DIGEST_SPLIT", 128)
    values = [_bytes(64 * k + k % 5, seed=k) for k in range(3, 35)]
    want = [_chain(v, k) for k, v in enumerate(values)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(16) as callers:
            got = list(callers.map(unit_checksum, values,
                                   range(len(values)), timeout=60))
    finally:
        sys.setswitchinterval(interval)
    assert got == want
