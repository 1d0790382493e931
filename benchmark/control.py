"""The control of the comparison that decides `correct`: the plain
reference put in the program's place and computed in the nearest precision
below the one the deployments run in (bfloat16 prices: filter1q states
`price float`, and pattern1k's DOUBLE is evaluated in f32 on the device, as
its file states), held to the same comparison.  It has to come out
as NOT correct; the same reference at full precision has to come out
correct.  Host arithmetic only: no device is touched.

    python -m benchmark.control --workload pattern1k.sat --seeds 1,2,3 --batches 8
"""
import argparse
import json
import sys

import numpy as np


def _lowered(price):
    from ml_dtypes import bfloat16
    return np.asarray(price).astype(bfloat16)


def stand_in(cell: dict, seed: int, n_batches: int, lower: bool) -> list:
    """The checks that a run would print had the program delivered exactly
    what the reference computes (`lower`: on bfloat16 prices).  How a
    reference is put in the program's place is its module's `stand_in`."""
    from benchmark import engine, manifest
    cfg = cell["config"]
    tape = engine.tape_of(cell, seed)
    ref = manifest.module("reference", cfg["reference"])
    judge = ref.Judge(cfg, tape, seed)
    ref.stand_in(judge, [tape.batch(i) for i in range(n_batches)],
                 _lowered if lower else np.asarray)
    return judge.judge(n_batches)


def main(argv=None) -> int:
    from benchmark import compare, manifest
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--batches", type=int, default=8)
    args = ap.parse_args(argv)
    cell = manifest.Manifest().cell(args.workload)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for lower in (False, True):
            checks = stand_in(cell, seed, args.batches, lower)
            correct = compare.verdict(checks)
            ok &= correct != lower
            print(json.dumps({
                "workload": args.workload, "seed": seed,
                "in_program_place": "reference on bfloat16 prices (control)"
                if lower else "reference at full precision",
                "correct": correct,
                "compared": {c["name"]: [c["value"], c["limit"]]
                             for c in checks}}), flush=True)
    print("control", "FAILED THE COMPARISON AS IT MUST" if ok
          else "DID NOT SEPARATE FROM THE SOUND REFERENCE")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
