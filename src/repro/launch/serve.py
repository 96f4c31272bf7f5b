"""Serving driver CLI: run the LayerKV engine on a synthetic workload
through a live session — requests are submitted online and every
generated token is printed as its iteration produces it.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke \
        --policy layerkv --requests 16 --device-blocks 64

All six scheduling axes are exposed: --policy, --no-slo-aware,
--chunked, --fused, --prefix-cache, --preemption (plus --chunk-size for
the chunked per-iteration token budget), the admission ordering
(--admission fcfs|prefix_aware|deadline), and --interactive-every to
tag every k-th request as a priority-1 interactive request with a tight
deadline. `--replicas N` serves through a `ClusterSession`
over N identical engines with a pluggable dispatch policy (--router
round_robin|least_loaded|prefix_affinity|slo_aware); a cluster of 1 is
bit-identical to a bare session. Real JAX execution with paged KV
pools; prints the per-token stream, per-request TTFT, a per-replica
occupancy/hit-rate line at drain, and the offload-ledger summary.

Fault tolerance: `--fault-plan SPEC` injects deterministic failures on
the shared virtual clock (grammar: `crash@0.5:r0:recover=1.0;
wedge@0.2:r1:dur=0.3` or `random:SEED[:n=N]` — serving/faults.py);
`--liveness-timeout` arms missing-heartbeat detection, `--shed-overload`
turns wedging overload into typed request shedding. The drain report
then includes the recovery trace and kill/retry/shed counters.

The model serves in its config's dtype (bf16 for every shipped config).
With `--replicas N > 1` replica i is pinned to `jax.devices()[i]` — one
engine per chip — and N may not exceed the device count (on a CPU host,
`XLA_FLAGS=--xla_force_host_platform_device_count=N` provides N devices).
JAX's persistent compilation cache goes where `JAX_COMPILATION_CACHE_DIR`
says, else to `.jax_cache/` at the checkout root.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import statistics

import numpy as np

_CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def setup_compile_cache(root: pathlib.Path = _CHECKOUT_ROOT) -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory: `JAX_COMPILATION_CACHE_DIR` when set (JAX reads
    it itself; nothing else is set here), otherwise the fixed `.jax_cache/`
    under `root` — a fixed path, since the path is part of what makes a
    later run find the entries. Entry points call this; module import
    never does."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="layerkv",
                    choices=["layerkv", "vllm"])
    ap.add_argument("--no-slo-aware", action="store_true")
    ap.add_argument("--chunked", action="store_true",
                    help="chunked prefill + mixed batching")
    ap.add_argument("--fused", action="store_true",
                    help="ONE forward per iteration (implies --chunked)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="ref-counted cross-request prefix sharing")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="per-iteration prefill token budget (chunked)")
    ap.add_argument("--admission", default="fcfs",
                    choices=["fcfs", "prefix_aware", "deadline"],
                    help="waiting-queue admission ordering")
    ap.add_argument("--preemption", action="store_true",
                    help="lossless priority preemption: pause "
                         "lower-priority KV to HOST, resume later "
                         "(pairs with --admission deadline)")
    ap.add_argument("--interactive-every", type=int, default=0,
                    help="every k-th request is interactive: priority 1, "
                         "TTFT SLO (and deadline) tightened 4x (0 = all "
                         "batch)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the cluster router")
    ap.add_argument("--router", default="round_robin",
                    choices=["round_robin", "least_loaded",
                             "prefix_affinity", "slo_aware"],
                    help="cluster dispatch policy (--replicas > 1)")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault injection, e.g. "
                         "'crash@0.5:r0:recover=1.0;wedge@0.2:r1:dur=0.3' "
                         "or 'random:SEED[:n=N]' (serving/faults.py)")
    ap.add_argument("--liveness-timeout", type=float, default=None,
                    help="kill any replica whose next due event lags the "
                         "shared clock by more than this many seconds "
                         "while frozen (heartbeat failure detection)")
    ap.add_argument("--shed-overload", action="store_true",
                    help="graceful degradation: shed blocked requests "
                         "with a typed reason instead of wedging")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--shared-len", type=int, default=0,
                    help="leading tokens shared by every prompt "
                         "(exercises --prefix-cache)")
    ap.add_argument("--output-len", type=int, default=16)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--device-blocks", type=int, default=64)
    ap.add_argument("--host-blocks", type=int, default=1024)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the per-token stream printout")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the full event stream (lifecycle spans, "
                         "scheduler decision records, TTFT attribution) "
                         "and write Chrome-trace JSON here at drain — "
                         "load it at ui.perfetto.dev")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from repro.configs import get_config, get_smoke_config
    from repro.serving.cluster import ClusterSession
    from repro.serving.engine import LayerKVEngine
    from repro.serving.faults import FaultPlan
    from repro.serving.request import Request
    from repro.serving.scheduler import ServeConfig

    if not 0 <= args.shared_len < args.prompt_len:
        ap.error(f"--shared-len {args.shared_len} must be in "
                 f"[0, --prompt-len {args.prompt_len}): every prompt "
                 "needs at least one unique token")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    setup_compile_cache()
    if args.replicas > len(jax.devices()):
        ap.error(f"--replicas {args.replicas} exceeds the "
                 f"{len(jax.devices())} device(s): one engine per device")
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    rng = np.random.RandomState(args.seed)
    shared = [int(x) for x in
              rng.randint(0, cfg.vocab_size, args.shared_len)]
    t = 0.0
    reqs = []
    for i in range(args.requests):
        t += rng.exponential(1.0 / args.rate)
        sfx = args.prompt_len - len(shared)
        interactive = args.interactive_every > 0 \
            and i % args.interactive_every == 0
        reqs.append(Request(
            rid=f"r{i}", prompt_len=args.prompt_len,
            output_len=args.output_len, arrival=t,
            priority=1 if interactive else 0,
            ttft_slo=3.0 / 4 if interactive else 3.0,
            prompt=shared + [int(x) for x in
                             rng.randint(0, cfg.vocab_size, sfx)]))

    sc = ServeConfig.for_engine(
        policy=args.policy,
        slo_aware=not args.no_slo_aware,
        chunked=args.chunked or args.fused,
        fused=args.fused,
        prefix_cache=args.prefix_cache,
        preemption=args.preemption,
        admission=args.admission,
        max_prefill_tokens=args.chunk_size,
        num_device_blocks=args.device_blocks,
        num_host_blocks=args.host_blocks,
        block_size=args.block_size,
        shed_overload=args.shed_overload,
        trace=bool(args.trace))
    plan = FaultPlan.parse(args.fault_plan, n_replicas=args.replicas) \
        if args.fault_plan else None
    if plan is not None:
        print("fault plan:")
        for line in plan.describe():
            print(f"  {line}")
    # every replica loads the SAME weights (one PRNG seed): a cluster is
    # N copies of one model behind a router, not N different models —
    # each on its own device when there are several
    devices = jax.devices()[:args.replicas] if args.replicas > 1 else [None]
    engines = [LayerKVEngine(cfg, None, sc, rng=jax.random.PRNGKey(args.seed),
                             device=d)
               for d in devices]

    # submit everything up front (arrivals dispatch as the shared clock
    # reaches them) and pump the cluster one event at a time, printing
    # the token stream live as each iteration produces it
    session = ClusterSession(engines, router=args.router,
                             fault_plan=plan,
                             liveness_timeout=args.liveness_timeout)
    handles = [session.submit(r, arrival=r.arrival) for r in reqs]
    while session.step():
        for h in handles:
            new = h.take_new()
            if new and not args.quiet:
                star = "*" if h.request.cached_prompt_len else " "
                where = "?" if h.replica is None else h.replica
                print(f"[t={session.clock() * 1e3:9.3f}ms] {h.rid:>4}{star}"
                      f"@{where} +{len(new)} -> {new}")
    done = session.drain()

    ttfts = [r.ttft for r in done]
    print(f"policy={args.policy} chunked={args.chunked or args.fused} "
          f"fused={args.fused} prefix_cache={args.prefix_cache} "
          f"preemption={args.preemption} admission={args.admission} "
          f"replicas={args.replicas} router={args.router}")
    if args.preemption:
        print(f"preemptions={sum(e.core.n_preempted for e in engines)} "
              f"resumes={sum(e.core.n_resumed for e in engines)}")
    if ttfts:
        print(f"requests={len(done)} "
              f"mean_ttft={statistics.mean(ttfts)*1e3:.1f}ms "
              f"p99_ttft={sorted(ttfts)[-1]*1e3:.1f}ms")
    if session.recovery_log or plan is not None:
        shed = len(session.shed) \
            + sum(len(e.core.shed) for e in engines)
        print(f"faults: kills={session.n_kills} "
              f"recoveries={session.n_recoveries} "
              f"redispatched={len(session.redispatch_priorities)} "
              f"dispatch_retries={session.n_retries} shed={shed}")
        for line in session.recovery_log:
            print(f"  {line}")
    for i, (eng, st) in enumerate(zip(engines, session.stats)):
        served = len(eng.core.done)
        hit = f"{eng.bm.cache.hit_rate:.2f}" \
            if eng.bm.cache is not None else "-"
        print(f"replica {i}: dispatched={st.dispatched} served={served} "
              f"iterations={st.steps} "
              f"peak_occupancy={st.peak_occupancy:.2f} "
              f"prefix_hit_rate={hit}")
    off = [x for eng in engines for x in eng.off.ledger.log
           if x.kind == "offload"]
    rel = [x for eng in engines for x in eng.off.ledger.log
           if x.kind == "reload"]
    print(f"layer-wise transfers: {len(off)} offloads "
          f"({sum(x.nbytes for x in off)/2**20:.2f} MiB), "
          f"{len(rel)} reloads "
          f"({sum(x.nbytes for x in rel)/2**20:.2f} MiB)")
    if args.trace:
        session.write_trace(args.trace)
        n_ev = sum(len(e.core.tracer.events) for e in engines) \
            + len(session.tracer.events)
        print(f"trace: {n_ev} events -> {args.trace} "
              "(load at ui.perfetto.dev)")
    if done:
        sample = done[0]
        print(f"sample output ({sample.rid}): {sample.generated[:8]}...")


if __name__ == "__main__":
    main()
