#!/usr/bin/env python3
"""One run of one benchmark cell of the out-of-core stencil engine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. It names a
configuration (``bench/configs/<name>.json``: the physics, the field
codes, ``bt``, the backend and the limits of the check) and a job
(``bench/jobs/<name>.json``: the volume, the blocks, the schedule, the
residency budget and the warm-up). Per-layer metrics are read by
``bench/metrics/<metric>.py``. A new cell, job, configuration or metric
is a new file and a new entry; nothing here changes.

Set-up: the seeded fields are made on the device in one jitted call
(``reference.fields``), the engine is seeded from them through its
constructor, and ``warm_rounds`` whole rounds run, so that every
program and every shape the window uses has been compiled (and, for a
resident job, the residency is filled). The engine is
``AsyncExecutor``, or, for a job whose file sets ``"shards": N``,
``ShardedExecutor`` with one shard on each of the cell's N chips. The
window then runs whole rounds (``advance_round``, the loop ``run`` is
built from, or the sharded coordinator's ``sweep``) until
``--seconds`` have passed, drains the engine and waits until the
device has finished; ``gpts_per_s`` is every point update of the
window over the whole window.

After the window the engine's fields over a region that straddles a
block boundary near the pulse (a shard boundary, where the halo lands,
for a sharded job; placed from the seed) are read from the shards that
own them and compared with ``reference.run_reference`` over the
region's dependency cone, run once the engine's device arrays are
freed.

With ``--trace 1`` the window runs under the profiler, the benchmark
wraps the host store's ``stage``/``put`` and the executor's calls of
the stencil and codec programs in spans (and records each call's
shapes), the engine's own ``ooc.*`` spans are kept beside them, and the
per-layer metrics are printed instead of the end-to-end ones.

Earlier lines on standard error report what is not a metric; the last
lines there are the compared numbers beside their limits. The last
line of standard output is the result, a JSON object. Without a TPU,
or with fewer chips than the cell asks for, or outside a checkout of
the engine, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import numbers  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SRC = ROOT / "src"
FAR = 1 << 40  # a round target no run reaches: advance_round never truncates
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot be made here (no chip, no engine, no such cell)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_start() -> float:
    """When this process started, on the ``time.time`` clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------


def load_cell(name: str, root: Path = ROOT) -> Dict[str, object]:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    its job and the metrics it reports."""
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise Refused(f"no {path}")
    spec = json.loads(path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    job = json.loads(
        (root / "bench" / "jobs" / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"cell": cell, "config": config, "job": job,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def load_reader(metric: str, root: Path = ROOT):
    """``read(record)`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def check_region(job, seed: int):
    """The checked region, ``((z0, z1), (y0, y1), (x0, x1))``: ``region``
    planes straddling a block boundary in the middle half of the
    volume (for a job with ``shards``, a boundary between two shards,
    where the halo lands), and a ``region``-sized square of Y and X
    near the centre, both drawn from the seed and on the 4-grid."""
    import numpy as np

    rng = np.random.default_rng((seed, 7))
    (z, y, x), ndiv = job["shape"], job["ndiv"]
    dz, dy, dx = job["region"]
    block = z // ndiv
    shards = job.get("shards", 1)
    # the engine's balanced split: shard d holds blocks from d*ndiv//N
    cuts = (range(1, ndiv) if shards == 1
            else [d * ndiv // shards for d in range(1, shards)])
    edges = [k * block for k in cuts
             if abs(k * block - z / 2) <= z / 4] or [block * (ndiv // 2)]
    edge = int(rng.choice(edges))
    out = [(edge - dz // 2, edge + dz // 2)]
    for n, d in ((y, dy), (x, dx)):
        lo, hi = max(0, n // 2 - 2 * d), max(0, min(n - d, n // 2 + d))
        o = 4 * int(rng.integers(lo // 4, hi // 4 + 1))
        out.append((o, o + d))
    return tuple(out)


# ---------------------------------------------------------------------------
# tracing hooks (traced runs only)
# ---------------------------------------------------------------------------


def _leaves(tree):
    import jax

    return [[list(a.shape), str(a.dtype)]
            for a in jax.tree_util.tree_leaves(tree) if hasattr(a, "shape")]


@contextlib.contextmanager
def hooks(calls: Dict[str, List[dict]]):
    """Wrap the program's layer entries the executor calls in host
    spans, and record each program call's shapes in ``calls``. A name
    the program no longer has is left alone, and its metric reads
    nothing."""
    import jax

    from repro.core import outofcore
    from repro.kernels.stencil import ops as stencil_ops
    from repro.kernels.zfp import ops as zfp_ops

    undo = []

    def wrap(owner, attr, span, record=None):
        orig = getattr(owner, attr, None)
        if orig is None:
            return

        def wrapped(*args, **kwargs):
            with jax.profiler.TraceAnnotation(span):
                out = orig(*args, **kwargs)
            if record is not None:
                calls.setdefault(record, []).append({
                    "in": _leaves(args), "out": _leaves(out),
                    "kw": {k: v for k, v in kwargs.items()
                           if isinstance(v, (int, str, float))},
                })
            return out

        setattr(owner, attr, wrapped)
        undo.append((owner, attr, orig))

    store = getattr(outofcore, "HostUnitStore", None)
    if store is not None:
        wrap(store, "stage", "bench.store.stage")
        wrap(store, "put", "bench.store.put")
    wrap(stencil_ops, "fused_temporal_steps", "bench.dispatch.stencil",
         "fused_temporal_steps")
    wrap(zfp_ops, "compress", "bench.dispatch.encode", "compress")
    wrap(zfp_ops, "decompress", "bench.dispatch.decode", "decompress")
    try:
        yield
    finally:
        for owner, attr, orig in reversed(undo):
            setattr(owner, attr, orig)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def wait_device() -> None:
    """Wait until every array on the device is computed."""
    import jax

    for a in jax.live_arrays():
        a.block_until_ready()


def shard_devices(job, cell, devices, allow_cpu: bool):
    """The devices a sharded job's shards are pinned to, one to each of
    the cell's chips; None for a job without ``shards``, and under
    ``allow_cpu``, where the shards share the one device. A job whose
    shards are not the cell's chips is refused."""
    shards = job.get("shards", 1)
    if allow_cpu:
        return None
    if shards != cell["chips"]:
        raise Refused(f"the job has {shards} shard(s) and the cell asks "
                      f"for {cell['chips']} chip(s)")
    return list(devices[:shards]) if shards > 1 else None


def engine_config(config, job):
    """The engine's ``OOCConfig`` of a configuration and a job."""
    from repro.core.outofcore import FieldSpec, OOCConfig

    return OOCConfig(
        tuple(job["shape"]), job["ndiv"], config["bt"],
        {n: FieldSpec(f["role"], f["planes"])
         for n, f in config["fields"].items()},
        backend=config["backend"], dtype=config["dtype"],
    )


def build_engine(cfg, job, p, v, devices):
    """The engine a job runs on and a call that advances it one round:
    ``AsyncExecutor`` and its ``advance_round``, or, for a job with
    ``shards`` over 1, ``ShardedExecutor`` (``cache_bytes`` per device)
    and its ``sweep``."""
    kw = {"schedule": job["schedule"], "cache_bytes": job["cache_bytes"]}
    shards = job.get("shards", 1)
    if shards > 1:
        from repro.core.sharded import ShardedExecutor

        eng = ShardedExecutor(cfg, p, p, v, nshards=shards, devices=devices,
                              **kw)
        return eng, eng.sweep
    from repro.core.executor import AsyncExecutor

    eng = AsyncExecutor(cfg, p, p, v, **kw)
    return eng, functools.partial(eng.advance_round, FAR)


def owners(eng):
    """``[(store, device, units)]``: each host store with the units
    ``(kind, idx, (lo, hi))`` it holds the committed values of, and the
    device its engine runs on. A sharded engine's units are read from
    the shard that owns them, never from a neighbour's ghost mirror."""
    units = eng.plan.units()
    if not hasattr(eng, "specs"):
        return [(eng.store, None, units)]
    out = []
    for spec, ex in zip(eng.specs, eng.shards):
        owned = set(spec.owned_units())
        out.append((ex.store, spec.device,
                    [u for u in units if u[:2] in owned]))
    return out


def read_region(eng, region):
    """The engine's p_prev and p_cur over ``region``, from the host
    store of each unit's owner after a flush, compressed units decoded
    by the engine's own decoder on the owner's device."""
    import jax
    import numpy as np

    from repro.kernels.zfp import ops as zfp_ops
    from repro.kernels.zfp.ref import Compressed

    eng.finish()
    eng.flush()
    (z0, z1), (y0, y1), (x0, x1) = region
    out = {}
    for name in ("p_prev", "p_cur"):
        got = np.empty((z1 - z0, y1 - y0, x1 - x0), np.float32)
        for store, device, units in owners(eng):
            for kind, idx, (lo, hi) in units:
                a, b = max(lo, z0), min(hi, z1)
                if a >= b:
                    continue
                unit = store.get(name, kind, idx)
                if isinstance(unit, Compressed):
                    with (jax.default_device(device) if device is not None
                          else contextlib.nullcontext()):
                        unit = zfp_ops.decompress(unit,
                                                  backend=eng.cfg.backend)
                got[a - z0 : b - z0] = np.asarray(unit)[a - lo : b - lo,
                                                        y0:y1, x0:x1]
        out[name] = got
    return out


def residency(eng) -> Dict[str, int]:
    """Residency hits, misses and elided D2H since seeding, summed over
    the shards of a sharded engine."""
    stats = eng.stats()
    per = stats["per_device"].values() if "per_device" in stats else [stats]
    return {k: sum(s["cache"][k] for s in per)
            for k in ("hits", "misses", "d2h_elided")}


def run_cell(loaded, seed: int, seconds: float, trace: bool,
             allow_cpu: bool = False) -> Dict[str, object]:
    """Set up, measure and check one run; returns the result object.
    ``allow_cpu`` skips the look for a chip (rehearsals and tests)."""
    if not (SRC / "repro" / "core" / "executor.py").is_file():
        raise Refused(f"{SRC} holds no checkout of the engine")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import jax
    import numpy as np

    cell, config, job = loaded["cell"], loaded["config"], loaded["job"]
    devices = jax.devices()
    dev = devices[0]
    if not allow_cpu:
        if dev.platform != "tpu":
            raise Refused(f"no TPU: JAX found {dev.platform} "
                          f"({dev.device_kind})")
        if len(devices) < cell["chips"]:
            raise Refused(f"the cell asks for {cell['chips']} chips, JAX "
                          f"found {len(devices)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if trace and dev.device_kind not in peaks and not allow_cpu:
        raise Refused(f"no peaks for device kind {dev.device_kind!r} in "
                      "bench/peaks.json")

    from bench import reference
    from bench import spans
    from bench import traces as tr

    pinned = shard_devices(job, cell, devices, allow_cpu)

    # the compile cache lives at a fixed path in the checkout, every
    # program in it, and the program takes the same directory
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; compile cache {cache}")

    compiles = {"window": 0, "open": False}

    def on_compile(event, duration, **_):
        if event == COMPILE_EVENT and compiles["open"]:
            compiles["window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    # ---- set-up -----------------------------------------------------
    shape = tuple(job["shape"])
    cfg = engine_config(config, job)
    t = time.time()
    p, v = reference.fields(shape, seed)
    p, v = np.asarray(p), np.asarray(v)
    t_fields = time.time() - t
    t = time.time()
    eng, advance = build_engine(cfg, job, p, v, pinned)
    del p, v
    t_seed = time.time() - t
    t = time.time()
    for _ in range(job["warm_rounds"]):
        advance()
    eng.finish()
    wait_device()
    log(f"set-up parts: fields to host {t_fields!r} s, seeding "
        f"{t_seed!r} s, {job['warm_rounds']} warm round(s) "
        f"{time.time() - t!r} s")
    before = eng.transfer_summary()
    sweeps0 = eng.sweeps_done

    # ---- the window -------------------------------------------------
    calls: Dict[str, List[dict]] = {}
    traced: Dict[str, object] = {}
    rounds = 0
    compiles["open"] = True
    setup_s = time.time() - process_start()
    with contextlib.ExitStack() as stack:
        if trace:
            stack.enter_context(tr.capture(traced))
            stack.enter_context(hooks(calls))
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            t0 = time.time()
            while True:
                advance()
                rounds += 1
                if time.time() - t0 >= seconds:
                    break
            eng.finish()
            wait_device()
            t1 = time.time()
    compiles["open"] = False
    window_s = t1 - t0
    steps = (eng.sweeps_done - sweeps0) * config["bt"]
    after = eng.transfer_summary()
    moved = {k: n - before.get(k, 0) for k, n in after.items()
             if isinstance(n, numbers.Number)}
    chip_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices[: cell["chips"]]]
    peak = max(chip_peaks)
    points = math.prod(shape)
    log(f"window: {rounds} rounds, {steps} steps of {points} points in "
        f"{window_s!r} s; compilations in the window: {compiles['window']}")
    cache_stats = residency(eng)
    log(f"wire bytes in the window: h2d {moved['h2d_wire']} (raw "
        f"{moved['h2d_raw']}), d2h {moved['d2h_wire']} (raw "
        f"{moved['d2h_raw']}), halo {moved['halo_wire']} (raw "
        f"{moved['halo_raw']}, {moved['halo_count']} crossings); "
        f"residency since seeding: hits "
        f"{cache_stats['hits']}, misses {cache_stats['misses']}, d2h "
        f"elided {cache_stats['d2h_elided']}")
    log(f"set-up {setup_s!r} s; peak_bytes_in_use {peak} (each chip: "
        f"{chip_peaks})")

    # ---- the check --------------------------------------------------
    region = check_region(job, seed)
    got = read_region(eng, region)
    total_rounds = eng.sweeps_done
    del eng
    gc.collect()
    t_ref = time.time()
    want = reference.run_reference(
        shape, seed, region, total_rounds, config["bt"],
        {n: f["planes"] for n, f in config["fields"].items()})
    log(f"reference over the cone of {region} after "
        f"{total_rounds * config['bt']} steps: {time.time() - t_ref!r} s")
    checks = {
        f"err_{n}": {"value": reference.rel_err(got[n], want[n]),
                     "limit": config["limits"][n]}
        for n in ("p_prev", "p_cur")
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # ---- metrics ----------------------------------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": rounds, "failed": 0}
    if not trace:
        values = {"gpts_per_s": points * steps / window_s / 1e9,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in loaded["end_to_end"]}
    else:
        record = {"trace": traced, "calls": calls, "steps": steps,
                  "rounds": rounds, "window_s": window_s, "points": points,
                  "transfers": moved,
                  "peaks": peaks.get(dev.device_kind, {})}
        metrics = {}
        for m in loaded["per_layer"]:
            value = load_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        got_busy = tr.busy(traced)
        if got_busy is not None:
            device["busy_s"] = got_busy[0] / 1e9
            device["window_s"] = got_busy[1] / 1e9
        result["breakdown"] = {"device_ops": tr.top_ops(traced),
                               "idle_gaps": spans.idle_gaps(traced)}
    result.update(metrics=metrics, device=device, checks=checks)
    for name, c in checks.items():
        log(f"{name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(load_cell(args.workload), args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        log(f"FAIL: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
