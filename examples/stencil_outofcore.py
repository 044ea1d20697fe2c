"""The paper, end to end: the four experiment codes on a scaled volume,
run on BOTH engines — the synchronous reference and the async
double-buffered executor (bit-identical by construction) — plus the
paper-scale V100 pipeline projection.

  PYTHONPATH=src python examples/stencil_outofcore.py

Kill-and-resume via the crash-consistent checkpoint API
(docs/architecture.md): pass ``--checkpoint-dir`` to run the first
half of the steps, snapshot the in-flight executor (quiesce + ordered
flush + atomic persist), and exit — as if preempted. Rerun with
``--resume`` to restore into a fresh executor (fresh process, cold
device residency) and finish; the script verifies the resumed output
is bit-identical to an uninterrupted run:

  PYTHONPATH=src python examples/stencil_outofcore.py --checkpoint-dir ckpts
  PYTHONPATH=src python examples/stencil_outofcore.py --checkpoint-dir ckpts --resume
"""

import argparse

import numpy as np

from repro.compile_cache import place_compile_cache
from repro.core.executor import AsyncExecutor
from repro.core.outofcore import OOCConfig, OutOfCoreWave, \
    paper_code_fields
from repro.core.pipeline import V100_PCIE, sweep_timeline
from repro.distributed.fault import ReissuePolicy
from repro.kernels.stencil import ref as stencil_ref

SHAPE = (64, 32, 32)
NDIV, BT, STEPS = 2, 4, 24


def _initial():
    p_cur = np.asarray(stencil_ref.ricker_source(SHAPE), np.float32)
    p_prev = 0.97 * p_cur
    vel2 = np.full(SHAPE, 0.06, np.float32)
    return p_prev, p_cur, vel2


def paper_demo() -> None:
    import jax.numpy as jnp

    p_prev, p_cur, vel2 = _initial()
    ref_pp, ref_pc = stencil_ref.run_steps(
        jnp.asarray(p_prev), jnp.asarray(p_cur), jnp.asarray(vel2),
        STEPS,
    )

    print(f"volume {SHAPE}, ndiv={NDIV}, bt={BT}, {STEPS} steps")
    print(f"{'code':<6}{'h2d wire':>10}{'d2h wire':>10}"
          f"{'max rel err':>14}{'V100 speedup':>14}{'live==sync':>12}")
    base = eng = None
    for code in (1, 2, 3, 4):
        cfg = OOCConfig(SHAPE, NDIV, BT, paper_code_fields(code))
        eng = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
        eng.run(STEPS)
        # the live overlapped executor must reproduce the sync engine
        # bit for bit while streaming through the shared task graph
        live = AsyncExecutor(cfg, p_prev, p_cur, vel2, schedule="depth2")
        live.run(STEPS)
        identical = np.array_equal(
            live.gather("p_cur"), eng.gather("p_cur")
        )
        tot = eng.transfer_summary()
        err = float(
            np.abs(eng.gather("p_cur") - np.asarray(ref_pc)).max()
            / np.abs(np.asarray(ref_pc)).max()
        )
        # paper-scale projection
        tl = sweep_timeline(
            OOCConfig((1152,) * 3, 8, 12, paper_code_fields(code, False),
                      dtype="float64"),
            V100_PCIE, sweeps=4, schedule="paper",
        )
        if base is None:
            base = tl.makespan
        print(
            f"{code:<6}{tot['h2d_wire']/1e6:>9.2f}M"
            f"{tot['d2h_wire']/1e6:>9.2f}M"
            f"{err:>14.2e}{base/tl.makespan:>13.3f}x"
            f"{'yes' if identical else 'NO':>12}"
        )
    print("\n(code 1 = no compression; 2 = RW@2:1; 3 = RO@2:1; "
          "4 = RW+RO@2.67:1 — paper Fig. 5 measured 1.16/1.18/1.20x)")

    # beyond the paper: keep the working set device-resident under the
    # write-back policy — steady-state sweeps touch the wire in
    # NEITHER direction (fetches hit, writebacks commit on device);
    # the host only pays one flush of the dirty working set at gather.
    cfg = OOCConfig(SHAPE, NDIV, BT, paper_code_fields(4))
    res = AsyncExecutor(cfg, p_prev, p_cur, vel2, schedule="depth2",
                        cache_bytes=1 << 30, policy="write-back")
    res.run(STEPS)
    pre = res.transfer_summary()
    same = np.array_equal(res.gather("p_cur"), eng.gather("p_cur"))
    post = res.transfer_summary()
    steady = sum(t.wire_bytes for t in res.transfers
                 if t.sweep > 0 and not t.flush)
    print(
        f"\nwrite-back residency (code 4): steady h2d+d2h wire after "
        f"warmup = {steady}B, "
        f"gather flush = {post['d2h_flush_wire']}B "
        f"(write-through paid {eng.transfer_summary()['d2h_wire']}B "
        f"d2h), bit-identical: {'yes' if same else 'NO'}"
    )
    assert pre["d2h_wire"] == 0, pre


def checkpoint_demo(ckpt_dir: str, resume: bool) -> None:
    """Kill-and-resume: first half of the run + snapshot (as if
    preempted), or restore + second half + bit-exactness check."""
    p_prev, p_cur, vel2 = _initial()
    cfg = OOCConfig(SHAPE, NDIV, BT, paper_code_fields(2))
    half = STEPS // (2 * BT) * BT
    if not resume:
        live = AsyncExecutor(
            cfg, p_prev, p_cur, vel2, schedule="depth2",
            cache_bytes=1 << 30, reissue=ReissuePolicy(),
        )
        live.run(half)
        path = live.checkpoint(ckpt_dir)
        st = live.stats()["cache"]
        print(
            f"ran {half}/{STEPS} steps, snapshot at {path} "
            f"(flushed {st['flushes']} dirty units, "
            f"{st['flush_wire_bytes']}B); rerun with --resume to finish"
        )
        return
    live = AsyncExecutor.restore(ckpt_dir)
    done = live.sweeps_done * cfg.bt
    live.run(STEPS - done)
    resumed = live.gather("p_cur")
    # the ground truth: the same run, never interrupted
    ref = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    ref.run(STEPS)
    identical = np.array_equal(resumed, ref.gather("p_cur"))
    print(
        f"resumed at step {done}, ran to {STEPS}; bit-identical to "
        f"uninterrupted run: {'yes' if identical else 'NO'}"
    )
    assert identical


def main() -> None:
    place_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot the run here after STEPS/2 steps "
                         "(kill-and-resume demo)")
    ap.add_argument("--resume", action="store_true",
                    help="restore from --checkpoint-dir and finish")
    args = ap.parse_args()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    if args.checkpoint_dir:
        checkpoint_demo(args.checkpoint_dir, args.resume)
    else:
        paper_demo()


if __name__ == "__main__":
    main()
