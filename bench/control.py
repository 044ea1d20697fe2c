#!/usr/bin/env python3
"""The control of a cell's check: the reference put in the engine's
place and run in bfloat16, the precision below the float32 that the
configurations state.

    python bench/control.py --workload <cell> --rounds <n> --seeds 1 2 3

For each seed it computes, over that seed's checked region and at the
cell's own volume, the reference in float32 and in bfloat16 after
``--rounds`` rounds (a run's warm rounds and window rounds together),
and prints the same numbers a run compares, ``err_p_prev`` and
``err_p_cur``, beside the cell's limits. The control has to read above
a limit: one JSON line per seed, and a last line
``{"control_fails": true|false}``. Runs on the chip; the benchmark's
own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(loaded, seed: int, rounds: int):
    import jax.numpy as jnp

    from bench import reference
    from bench.run import check_region

    config, job = loaded["config"], loaded["job"]
    region = check_region(job, seed)
    planes = {n: f["planes"] for n, f in config["fields"].items()}
    args = (job["shape"], seed, region, rounds, config["bt"], planes)
    want = reference.run_reference(*args)
    got = reference.run_reference(*args, dtype=jnp.bfloat16)
    return {f"err_{n}": reference.rel_err(got[n], want[n])
            for n in ("p_prev", "p_cur")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import load_cell

    loaded = load_cell(args.workload)
    limits = loaded["config"]["limits"]
    fails = True
    for seed in args.seeds:
        got = readings(loaded, seed, args.rounds)
        over = any(v > limits[k[4:]] for k, v in got.items())
        fails = fails and over
        print(json.dumps({"seed": seed, "rounds": args.rounds, **got,
                          "limits": limits, "fails": over}), flush=True)
    print(json.dumps({"control_fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
