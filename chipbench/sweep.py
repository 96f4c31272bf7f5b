"""Find a cell's knee once: serve its traffic at several fixed rates in
one process and print, per rate, the end-to-end metrics and how TTFT
trends across the window (a queue that grows all through the window
means the rate is above what the system sustains).

    python3 chipbench/sweep.py --workload <cell> --rates 0.5,1,2 \
        --seconds 30 --seed 1

The benchmark's own runs never run this; a benchmark change that moves a
cell's rate records the sweep in PERF.md.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    import run
    bench = common.benchmark()
    cell = common.workload(bench, a.workload)
    cfile = common.config_file(bench, cell["config"])
    devices, peaks = run.find_chip(cell)
    for rate in (float(x) for x in a.rates.split(",")):
        mix = dict(common.traffic_file(cell["traffic"]), rate_rps=rate)
        args = run.parse(["--workload", a.workload, "--seed", str(a.seed),
                          "--seconds", str(a.seconds), "--trace", "0"])
        res, keep = run.run_cell(args, bench, cell, cfile, mix, devices,
                                 peaks, run.process_start())
        counted = [r for r in keep["recs"] if r.tr.in_window and r.times]
        counted.sort(key=lambda r: r.due)
        third = max(len(counted) // 3, 1)
        first = statistics.median(r.ttft for r in counted[:third]) \
            if counted else None
        last = statistics.median(r.ttft for r in counted[-third:]) \
            if counted else None
        print("SWEEP " + json.dumps({
            "rate_rps": rate, "attempted": res["attempted"],
            "failed": res["failed"], "correct": res["correct"],
            "ttft_p50_s": statistics.median(r.ttft for r in counted)
            if counted else None,
            "ttft_p50_first_third_s": first, "ttft_p50_last_third_s": last,
            **{k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
        keep.clear()
        jax.clear_caches()
        gc.collect()


if __name__ == "__main__":
    main()
