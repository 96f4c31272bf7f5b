"""The control of `correct`: the plain reference computed one precision
below the configuration's (fp8 matmul operands for a bf16 model), put in
the program's place, must read as not correct under the same limit.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed this runs the cell once (a normal run, `correct` and all),
then puts, on the same sampled prompts and served tokens, the token the
fp8 reference ranks first at each position through the same comparison
(`correctness.check`) in place of the served one. It prints one JSON
line per seed with the program's reading and verdict and the control's,
beside the limit they are held to. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402
import correctness  # noqa: E402
import reference  # noqa: E402


def fp8_tokens(dims, weights):
    """`chosen` for `correctness.check`: the fp8 reference's first-ranked
    token at each position of a sampled request."""
    def chosen(r):
        low = reference.teacher_forced_logits(dims, weights, r.tr.prompt,
                                              r.tokens, precision="fp8")
        return low.argmax(axis=1)
    return chosen


def read_seed(args, bench, cell, cfile, mix, devices, peaks) -> dict:
    import run
    res, kept = run.run_cell(args, bench, cell, cfile, mix, devices, peaks,
                             run.process_start())
    checks, ok, sampled = correctness.check(
        cfile, kept["dims"], kept["weights"], kept["recs"], args.seed,
        kept["reloaded"], chosen=fp8_tokens(kept["dims"], kept["weights"]))
    out = {"seed": args.seed, "correct": res["correct"],
           "program_gap": res["checks"]["logit_gap"]["value"],
           "control_correct": ok,
           "control_gap": checks["logit_gap"]["value"],
           "limit": checks["logit_gap"]["limit"],
           "tokens": checks["tokens_compared"]["value"],
           "sampled": sampled}
    kept.clear()
    jax.clear_caches()
    gc.collect()
    return out


def main(argv=None) -> None:
    import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    bench = common.benchmark()
    cell = common.workload(bench, a.workload)
    cfile = common.config_file(bench, cell["config"])
    mix = common.traffic_file(cell["traffic"])
    devices, peaks = run.find_chip(cell)
    for s in a.seeds.split(","):
        args = run.parse(["--workload", a.workload, "--seed", s,
                          "--seconds", str(a.seconds), "--trace", "0"])
        print("CONTROL " + json.dumps(read_seed(args, bench, cell, cfile,
                                                mix, devices, peaks)),
              flush=True)


if __name__ == "__main__":
    main()
