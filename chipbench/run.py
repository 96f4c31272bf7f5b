"""Chip benchmark: one run of one cell of `BENCHMARK.json`.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the program's serving engine on one chip with weights made from
the seed, warms every shape the seed's traffic will use, serves a
warm-in and then `--seconds` of open-loop traffic on the wall clock, and
prints one JSON line last on standard output. With `--trace 0` its
metrics are the cell's end-to-end metrics; with `--trace 1` the window
is profiled and its metrics are the cell's per-layer metrics. Every run
checks the served tokens against the plain reference (`correct`).

Exits non-zero, printing no result, without a TPU, with a device kind
that `chipbench/peaks.json` lacks, or where the program is missing.
"""
from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def process_start() -> float:
    """perf_counter() reading at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def find_chip(cell: dict):
    """The chip the cell runs on, with its peaks; exits without one.
    Also fixes JAX's persistent compile cache to a directory inside the
    checkout, so only a cell's first run in a checkout compiles; every
    program is kept, small ones too."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{cell['chips']} chips asked, "
                         f"{len(devices)} found")
    peaks = common.peaks(devices[0].device_kind)
    # read at the first compile, so set before any
    cache_dir = str(common.ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices, peaks


def main(argv=None) -> dict:
    """One run of the cell the arguments name; prints its result line."""
    t_proc0 = process_start()
    args = parse(argv)
    if not (common.ROOT / "src" / "repro").is_dir():
        raise SystemExit("the program (src/repro) is not in this checkout")
    bench = common.benchmark()
    cell = common.workload(bench, args.workload)
    cfile = common.config_file(bench, cell["config"])
    mix = common.traffic_file(cell["traffic"])
    devices, peaks = find_chip(cell)
    result, _ = run_cell(args, bench, cell, cfile, mix, devices, peaks,
                         t_proc0)
    print(json.dumps(result), flush=True)
    return result


def run_cell(args, bench: dict, cell: dict, cfile: dict, mix: dict,
             devices, peaks: dict, t_proc0: float):
    """Everything of a run after the look for a chip: build, warm, serve,
    read the metrics, free the pools, check against the reference.
    Returns the result and the run's records (recs, weights, dims)."""
    import jax
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")

    import harness
    import correctness
    from harness import RunData
    compiles = harness.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles.listener)

    dims = common.model_dims(cfile)
    reqs = harness.traffic_for(cfile, mix, args.seed, args.seconds)
    eng, weights, sizes = harness.build_engine(
        cfile, args.seed, dev, harness.max_pad(cfile, reqs),
        harness.max_blocks(cfile, reqs))
    log(f"pools (blocks of {eng.ec.block_size} tokens): {sizes}")
    warmed = harness.warm_up(eng, cfile, reqs)
    log(f"warmed: {warmed}")
    reloaded = harness.watch_reloads(eng)

    origin = time.perf_counter()
    recs = harness.make_records(reqs, origin)
    w0 = origin + float(mix["warm_in_s"])
    w1 = w0 + args.seconds
    trace_dir = str(common.ROOT / ".chipbench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    on_open = on_close = None
    span_t = [0.0, 0.0]
    spans = harness.host_spans(eng) if args.trace \
        else contextlib.nullcontext()
    if args.trace:
        def on_open():
            jax.profiler.start_trace(trace_dir)
            span_t[0] = time.perf_counter()

        def on_close():
            jax.block_until_ready(eng.ex.device_pool)
            span_t[1] = time.perf_counter()
            jax.profiler.stop_trace()
    with spans:
        steps = harness.serve(eng, recs, w0, w1, float(mix["tail_s"]),
                              on_open, on_close)
    t_end = time.perf_counter()
    ms = dev.memory_stats() or {}
    mem = ms.get("peak_bytes_in_use", 0)
    # the TPU runtime keeps programs' scratch apart from the arrays, as
    # reserved bytes: the HBM a run fills is the two together
    log(f"HBM: peak in use {mem}, peak reserved for program scratch "
        f"{ms.get('peak_bytes_reserved', 0)}, "
        f"limit {ms.get('bytes_limit', 0)}")
    lags = [r.submit_lag for r in recs if r.handle is not None]
    print(f"generator lag: max {max(lags, default=0.0):.6f} s, mean "
          f"{sum(lags) / max(len(lags), 1):.6f} s over {len(lags)} "
          f"submissions", flush=True)
    jit_sigs = sorted(str(s) for s in eng.ex._jit_sigs)
    log(f"executor signatures ({len(jit_sigs)}): {' '.join(jit_sigs)}")
    log(f"compiles in window: {compiles.count(w0, w1)}; "
        f"served until {t_end - w1:.3f} s after the window")

    run = RunData(cell=cell, dims=dims, peaks=peaks, reqs=recs,
                  steps=steps, w0=w0, w1=w1, setup_s=w0 - t_proc0,
                  compiles=compiles)
    if args.trace:
        import trace as trace_mod
        run.trace = trace_mod.reduce_dir(trace_dir, span_t[1] - span_t[0])
        run.trace.host_t0, run.trace.host_t1 = span_t
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = compute_metrics(bench, cell, run, args.trace)

    harness.free_engine(eng)
    checks, ok, sampled = correctness.check(cfile, dims, weights, recs,
                                            args.seed, reloaded)
    log(f"sampled: {sampled}")
    counted = run.counted()
    failed = sum(1 for r in counted if not r.times
                 or (r.handle is not None and (r.handle.shed
                                               or r.handle.cancelled)))
    result = {
        "correct": bool(ok),
        "attempted": len(counted),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(mem)},
    }
    if args.trace:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["sampled"] = sampled
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result, {"recs": recs, "weights": weights, "dims": dims,
                    "cfile": cfile, "reloaded": reloaded}


def compute_metrics(bench: dict, cell: dict, run, trace: int) -> dict:
    """End-to-end metrics of the cell (untraced run) or its per-layer
    metrics (traced run), each by its reader in `chipbench/metrics/`; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        v = common.metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


if __name__ == "__main__":
    try:
        main()
    except SystemExit as e:
        if e.code not in (None, 0):
            print(f"chipbench: {e.code}", file=sys.stderr)
            sys.exit(1)
        raise
