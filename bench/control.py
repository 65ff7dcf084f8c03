"""Readings that a cell's correctness limit is set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --seconds 10

In one process (the engine's executables compile once), for each seed:
one run of the cell as the benchmark makes it (the program in float32,
as configured), then, for the control seeds, one run of the program's
own lower-precision path (weights and KV cache in bfloat16) on the same
traffic.  Each run prints one JSON line with the gap readings of its
served tokens against the float32 reference.  For the float32 runs the
line also gives the readings of the reference itself, computed at each
of ``correctness.CONTROLS`` (int8 weights, bfloat16, float32 at default
matmul precision, and for a configuration with q/k/v biases float32
without them) and put in the program's place on the same context.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    import jax.numpy as jnp

    from bench import run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    run.check_device(1)
    run.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    for kind, dtype, group in (("program_f32", None, seeds),
                               ("control_bf16", jnp.bfloat16, control)):
        for seed in group:
            ns = argparse.Namespace(workload=args.workload, seed=seed,
                                    seconds=args.seconds, trace=0,
                                    keep_trace="")
            res = run.run(ns, dtype=dtype,
                          reference_control=dtype is None)
            line = {"kind": kind, "seed": seed, "correct": res["correct"],
                    **{k: v["value"] for k, v in res["checks"].items()},
                    **res["readings"]}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
