"""Serving driver: batched decode with the continuous-batching engine,
or (``--ooc``) the multi-tenant out-of-core stencil scheduler.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --requests 6 --max-new 8
  PYTHONPATH=src python -m repro.launch.serve --ooc --tenants 3 \
      --sweeps 4 --budget-mult 1.5
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke
from repro.models import model as M
from repro.serving.engine import ServeEngine


def run_ooc(args) -> None:
    """Multi-tenant out-of-core serving: N independent stencil runs on
    one device budget, arbitrated by ``serving.ooc.TenantScheduler``.
    Tenant 0 is the latency class (high priority, working-set reserve);
    the rest are batch class (priority 0, burst-only)."""
    from repro.core.outofcore import OOCConfig, paper_code_fields
    from repro.core.tenancy import working_set_bytes
    from repro.serving.ooc import TenantScheduler

    shape = tuple(args.shape)
    schedules = ["depth2", "temporal2", "unitgrain"]
    cfgs, specs = [], []
    for i in range(args.tenants):
        cfg = OOCConfig(shape, args.blocks, 1, paper_code_fields(2))
        sched_name = schedules[i % len(schedules)]
        cfgs.append((cfg, sched_name))
        specs.append(working_set_bytes(cfg, sched_name))
    budget = int(args.budget_mult * max(specs))
    eng = TenantScheduler(budget, admission="queue")
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i, (cfg, sched_name) in enumerate(cfgs):
        p_prev = rng.standard_normal(shape).astype(np.float32)
        p_cur = rng.standard_normal(shape).astype(np.float32)
        vel2 = (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)
        status = eng.submit(
            f"t{i}", cfg, p_prev, p_cur, vel2, schedule=sched_name,
            sweeps=args.sweeps,
            reserve=specs[i] if i == 0 else 0,
            priority=10 if i == 0 else 0,
        )
        print(f"tenant t{i}: {sched_name}, ws={specs[i]}B -> {status}")
    eng.run()
    dt = time.time() - t0
    st = eng.stats()
    print(f"{args.tenants} tenants, budget {budget}B, {dt:.2f}s wall")
    for name, ts in sorted(st["per_tenant"].items()):
        print(
            f"  {name}: sweeps={ts['sweeps_done']} hits={ts['hits']} "
            f"evictions={ts['evictions']} peak={ts['peak_bytes']}B "
            f"restarts={ts['restarts']}"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--ooc", action="store_true",
                    help="multi-tenant out-of-core stencil serving")
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--sweeps", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=2)
    ap.add_argument("--budget-mult", type=float, default=1.5)
    ap.add_argument("--shape", type=int, nargs=3, default=[32, 8, 8])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    if args.ooc:
        from repro.compile_cache import place_compile_cache

        place_compile_cache()
        run_ooc(args)
        return

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(
        cfg, params, slots=args.slots, max_len=args.max_len,
        temperature=args.temperature,
    )
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        prompt = rng.integers(
            1, cfg.vocab_size, size=rng.integers(2, 9)
        ).tolist()
        eng.submit(prompt, max_new=args.max_new)
    done = eng.run_all()
    dt = time.time() - t0
    toks = sum(len(v) for v in done.values())
    for rid, out in sorted(done.items()):
        print(f"request {rid}: {out}")
    print(f"{toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s, "
          f"{args.slots} slots)")


if __name__ == "__main__":
    main()
