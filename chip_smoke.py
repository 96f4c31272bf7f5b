#!/usr/bin/env python3
"""Chip smoke run: the LayerKV engine serving granite-3-2b at full width
(40 layers, d_model 2048, GQA 32/8, vocab 49155, bf16; random weights
from --seed) on a TPU v5e, through the entry points a user calls
(`LayerKVEngine` behind `ServingSession` / `ClusterSession`), with the
compiled Pallas paged kernels on the served path.

    python chip_smoke.py             # one chip: phases (a), (b), logits
    python chip_smoke.py --chips 4   # four one-chip replicas vs one

One chip:
  (a) exclusive prefill, layerkv policy, a device pool tight enough that
      layers really offload to the host tier and reload for decode,
      whose token KV is written in place (paged_kv_write);
  (b) chunked prefill + fused mixed step + prefix cache: paged_prefill
      (single-pool and host-tier variants) and paged_attention decode;
  logits: the fused path's prefill logits and a few decode steps' logits
      (fused and exclusive decode) against a plain float32 dense forward
      of the same weights on the host CPU.
`--chips 4`: four full-width replicas, each pinned to its own chip,
behind a `ClusterSession`, against the same requests on one replica.

Times are host wall clock around work that ends in `block_until_ready`
(the engine's own clock is a cost model and is never printed here).
Every check raises; the last line of standard output is the JSON result
only when all of them passed. Exits non-zero, printing no result, unless
JAX's first device is a TPU v5e.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
V5E_KINDS = ("TPU v5 lite", "TPU v5e")   # device_kind JAX reports for v5e
PROMPT_LEN = 256
OUTPUT_LEN = 12
BLOCK_SIZE = 16
# Served bf16 logits vs the float32 reference, as ||s - r|| / ||r||. bf16
# rounds each stored activation to an 8-bit significand (relative error
# <= 2**-9); 40 layers of ~10 rounded ops each, accumulating like a
# random walk, give sqrt(400) * 2**-9 ~ 4e-2 at worst. A wrong kernel is
# far off: reading each sequence's pages shifted by one gives ~1.1, a
# halved softmax scale ~0.35.
LOGITS_REL_L2_TOL = 5e-2


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def block(tree):
    import jax
    return jax.block_until_ready(tree)


def requests(tag, n, vocab, seed, shared=0):
    """n PROMPT_LEN-token prompts (the first `shared` tokens common to
    all), each asking OUTPUT_LEN tokens; generated from `seed`."""
    import numpy as np

    from repro.serving.request import Request
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, shared).tolist()
    return [Request(rid=f"{tag}{i}", prompt_len=PROMPT_LEN,
                    output_len=OUTPUT_LEN, arrival=i * 1e-6,
                    prompt=prefix + rng.randint(
                        0, vocab, PROMPT_LEN - shared).tolist())
            for i in range(n)]


def serve(session, reqs, engines):
    """Submit every request, drain, and check each one finished with its
    full output. Returns (done by rid, wall seconds)."""
    t0 = time.perf_counter()
    for r in reqs:
        session.submit(r, arrival=r.arrival)
    rids = {r.rid for r in reqs}
    done = [r for r in session.drain() if r.rid in rids]
    block([e.ex.device_pool for e in engines])
    dt = time.perf_counter() - t0
    check(len(done) == len(reqs),
          f"{len(done)} of {len(reqs)} requests finished")
    for r in done:
        check(len(r.generated) == r.output_len,
              f"{r.rid} generated {len(r.generated)} of {r.output_len}")
    return {r.rid: r for r in done}, dt


def retraces(engine) -> int:
    return sum(engine.ex.jit_retraces.values())


def run_phase(name, cfg, params, sc, reqs_fn, device):
    """Build an engine, serve the workload once to compile every shape it
    uses (set-up), then serve a fresh same-shaped workload (serve)."""
    from repro.kernels import ops
    from repro.serving.engine import LayerKVEngine
    from repro.serving.session import ServingSession
    t0 = time.perf_counter()
    eng = LayerKVEngine(cfg, params, sc, device=device)
    block(eng.ex.params)
    built = time.perf_counter() - t0
    before = dict(ops.dispatched)
    _, warm = serve(ServingSession(eng), reqs_fn("w"), [eng])
    n_compiled = retraces(eng)
    done, dt = serve(ServingSession(eng), reqs_fn("t"), [eng])
    traced = {k: v - before.get(k, 0) for k, v in ops.dispatched.items()
              if v > before.get(k, 0)}
    print(f"[{name}] wall clock: engine build {built:.3f} s, first serve "
          f"(compiles {n_compiled} signatures) {warm:.3f} s, second serve "
          f"{dt:.3f} s with {retraces(eng) - n_compiled} new signatures")
    print(f"[{name}] jit signatures per entry point: "
          f"{dict(eng.ex.jit_retraces)}")
    print(f"[{name}] attention ops traced (op, impl): {traced}")
    return eng, done, traced


def phase_exclusive(cfg, params, device, seed):
    """(a) Exclusive prefill, layerkv: ~1.6 prompts' KV fits the device
    pool, so later prompts keep only some layers on the device and the
    rest offload; decode reloads them."""
    from repro.serving.scheduler import ServeConfig
    per_prompt = cfg.n_layers * (PROMPT_LEN // BLOCK_SIZE)
    sc = ServeConfig.for_engine(
        policy="layerkv", slo_aware=False, chunked=False,
        num_device_blocks=per_prompt * 8 // 5,
        num_host_blocks=4 * (per_prompt + cfg.n_layers),
        block_size=BLOCK_SIZE)
    eng, _, _ = run_phase(
        "a exclusive layerkv", cfg, params, sc,
        lambda tag: requests(f"a{tag}", 4, cfg.vocab_size, seed), device)
    log = eng.off.ledger.log
    n_off = sum(x.kind == "offload" for x in log)
    n_rel = sum(x.kind == "reload" for x in log)
    print(f"[a exclusive layerkv] device pool {sc.num_device_blocks} "
          f"blocks; layer transfers in ledger: {n_off} offloads, "
          f"{n_rel} reloads")
    check(n_off > 0 and n_rel > 0, "phase (a) must offload and reload")


def phase_fused(cfg, params, device, seed):
    """(b) Chunked prefill + fused mixed step + prefix cache, layerkv.
    Six prompts share their first half; the pool holds ~2.4 prompts, so
    a late prompt's miss path offloads layers mid-prefill and its chunks
    read the host tier."""
    from repro.serving.scheduler import ServeConfig
    per_prompt = cfg.n_layers * (PROMPT_LEN // BLOCK_SIZE)
    sc = ServeConfig.for_engine(
        policy="layerkv", slo_aware=False, chunked=True, fused=True,
        prefix_cache=True, max_prefill_tokens=2 * PROMPT_LEN,
        num_device_blocks=per_prompt * 12 // 5,
        num_host_blocks=6 * (per_prompt + cfg.n_layers),
        block_size=BLOCK_SIZE)
    eng, done, traced = run_phase(
        "b chunked fused prefix", cfg, params, sc,
        lambda tag: requests(f"b{tag}", 6, cfg.vocab_size, seed + 1,
                             shared=PROMPT_LEN // 2), device)
    hits = sum(r.cached_prompt_len > 0 for r in done.values())
    host_steps = sum(1 for fn, sig in eng.ex._jit_sigs
                     if fn == "mixed" and sig[-1])
    print(f"[b chunked fused prefix] device pool {sc.num_device_blocks} "
          f"blocks; prefix hits {hits}/{len(done)}; host-tier fused "
          f"signatures {host_steps}")
    check(hits > 0, "phase (b) must hit the prefix cache")
    for op in ("paged_prefill", "paged_prefill_tiered", "paged_attention"):
        check(any(o == op for o, _ in traced), f"phase (b) must run {op}")


def check_logits(cfg, params, device, seed, n_steps=3):
    """Served-path logits against a float32 dense forward of the same
    weights (`model.prefill` / `model.decode`, jnp attention; on the host
    CPU where JAX has one, else on the chip at float32 matmul precision):
    the prompt's fused prefill through paged_prefill, again with the odd
    layers' KV written to the host tier (paged_prefill tiered) and
    reloaded to the device (`copy_blocks`), then n_steps decode steps
    through the fused step and the exclusive decode (paged_attention)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.serving.executor import MixedChunk, MixedDecode, \
        PagedExecutor
    L, V = cfg.n_layers, cfg.vocab_size
    prompt = requests("c", 1, V, seed + 2)[0].prompt
    nb = -(-(PROMPT_LEN + n_steps) // BLOCK_SIZE)
    live = -(-PROMPT_LEN // BLOCK_SIZE)
    ex = PagedExecutor(cfg, params, L * nb, L * nb, BLOCK_SIZE,
                       device=device)
    tabs = [list(range(l * nb, (l + 1) * nb)) for l in range(L)]
    host = [l % 2 == 1 for l in range(L)]
    out = {}

    def prefill(tiers):
        return ex.mixed_logits([MixedChunk(
            tokens=prompt, offset=0, tables=[t[:live] for t in tabs],
            tiers=tiers)], [])[0]

    out["prefill (paged_prefill)"] = prefill([False] * L)
    # the tiered run must find the odd layers' KV in the host pool only
    ex.device_pool = jnp.zeros_like(ex.device_pool)
    out["prefill (paged_prefill tiered)"] = prefill(host)
    for l in range(L):
        if host[l]:   # reload: same block ids in the other pool
            ex.copy_blocks("host", "device", tabs[l][:live], tabs[l][:live])
    toks = []
    for i in range(n_steps):
        toks.append(int(jnp.argmax(out["prefill (paged_prefill)"][:V]))
                    if i == 0 else int(jnp.argmax(out[f"decode {i} fused"]
                                                  [:V])))
        ctx = PROMPT_LEN + i
        out[f"decode {i + 1} fused"] = ex.mixed_logits(
            [], [MixedDecode(token=toks[-1], ctx=ctx, tables=tabs)])[0]
        out[f"decode {i + 1} exclusive"] = ex.decode_logits(
            [toks[-1]], np.asarray(tabs, np.int32)[:, None], [ctx])[0]
    served = {k: np.asarray(v[:V], np.float32)
              for k, v in block(out).items()}

    t0 = time.perf_counter()
    try:
        ref_dev = jax.devices("cpu")[0]
    except RuntimeError:             # no CPU backend beside the chip
        ref_dev = device
    m32 = build_model(dataclasses.replace(cfg, dtype="float32"))
    # float32 activations from a float32 embedding; every other weight
    # widens exactly from bf16 inside its float32 matmul
    p32 = dict(jax.device_put(params, ref_dev))
    p32["embed"] = p32["embed"].astype(jnp.float32)
    with jax.default_device(ref_dev), \
            jax.default_matmul_precision("highest"):
        cache = m32.init_cache(1, PROMPT_LEN + n_steps + 1, "float32")
        lg, cache = jax.jit(m32.prefill)(
            p32, {"tokens": jnp.asarray([prompt], jnp.int32)}, cache)
        ref = [np.asarray(lg[0, :V], np.float32)]
        dec = jax.jit(m32.decode)
        for t in toks:
            lg, cache = dec(p32, jnp.asarray([t], jnp.int32), cache)
            ref.append(np.asarray(lg[0, :V], np.float32))
    print(f"[logits] float32 dense reference on {ref_dev}, wall clock "
          f"{time.perf_counter() - t0:.3f} s; served in {cfg.dtype} on "
          f"{device}")
    worst = 0.0
    for name, s in served.items():
        r = ref[0] if name.startswith("prefill") else ref[int(name.split()[1])]
        e = float(np.linalg.norm(s - r) / np.linalg.norm(r))
        worst = max(worst, e)
        print(f"[logits] {name}: rel L2 {e:.6f}, max abs diff "
              f"{float(np.abs(s - r).max()):.6f} (max |ref| "
              f"{float(np.abs(r).max()):.4f}), top-1 "
              f"{'same' if s.argmax() == r.argmax() else 'differs'}")
    print(f"[logits] worst rel L2 {worst:.6f}, tolerance {LOGITS_REL_L2_TOL}")
    check(worst <= LOGITS_REL_L2_TOL, "served logits vs float32 reference")


def init_params(cfg, device, seed):
    """Random weights from `seed`, made on `device` by one compiled call
    (an eager init compiles each op on its own: ~65 s at full width)."""
    import jax

    from repro.models import build_model
    with jax.default_device(device):
        params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    return block(jax.device_put(params, device))


def one_chip(cfg, dev, seed):
    import jax

    from repro.kernels import ops
    t0 = time.perf_counter()
    params = init_params(cfg, dev, seed)
    devs = {d for leaf in jax.tree.leaves(params) for d in leaf.devices()}
    print(f"params: {cfg.param_count() / 1e9:.3f} B on {sorted(map(str, devs))}"
          f", init wall clock {time.perf_counter() - t0:.3f} s")
    check(devs == {dev}, "params must live on the chip")
    check_logits(cfg, params, dev, seed)
    phase_exclusive(cfg, params, dev, seed)
    phase_fused(cfg, params, dev, seed)
    print(f"attention implementations traced (op, impl): "
          f"{dict(ops.dispatched)}")
    for op in ("paged_attention", "paged_prefill", "paged_prefill_tiered",
               "paged_kv_write"):
        check(ops.dispatched[op, "pallas"] > 0, f"{op} must run Pallas")
        check(ops.dispatched[op, "ref"] == 0, f"{op} fell back to the ref")


def four_chips(cfg, seed):
    """Four full-width replicas, one per chip, behind a ClusterSession,
    against the same requests on one replica."""
    import jax

    from repro.serving.cluster import ClusterSession
    from repro.serving.engine import LayerKVEngine
    from repro.serving.scheduler import ServeConfig
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, "
          f"found {len(devices)}")
    per_prompt = cfg.n_layers * (PROMPT_LEN // BLOCK_SIZE)
    sc = ServeConfig.for_engine(
        policy="layerkv", slo_aware=False, chunked=False,
        num_device_blocks=4 * per_prompt, num_host_blocks=per_prompt,
        block_size=BLOCK_SIZE)
    t0 = time.perf_counter()
    # one set of weights, copied to each replica's chip by its engine
    params = init_params(cfg, devices[0], seed)
    reps = [LayerKVEngine(cfg, params, sc, device=d) for d in devices[:4]]
    block([e.ex.params for e in reps])
    print(f"[cluster] 4 replicas built, wall clock "
          f"{time.perf_counter() - t0:.3f} s")
    for i, e in enumerate(reps):
        devs = {d for leaf in jax.tree.leaves(e.ex.params)
                for d in leaf.devices()}
        pools = e.ex.device_pool.devices() | e.ex.host_pool.devices()
        print(f"[cluster] replica {i}: params on {sorted(map(str, devs))}, "
              f"pools on {sorted(map(str, pools))}")
        check(devs == pools == {devices[i]},
              f"replica {i} must live on device {i} only")
    mk = lambda: requests("q", 8, cfg.vocab_size, seed + 3)
    cl, dt4 = serve(ClusterSession(reps, router="round_robin"), mk(), reps)
    one = LayerKVEngine(cfg, params, sc, device=devices[0])
    ref, dt1 = serve(ClusterSession([one]), mk(), [one])
    served_by = {i: len(e.core.done) for i, e in enumerate(reps)}
    print(f"[cluster] wall clock (compiles included): 4 replicas "
          f"{dt4:.3f} s, 1 replica {dt1:.3f} s; requests per replica "
          f"{served_by}")
    check(all(n > 0 for n in served_by.values()), "every replica serves")
    first = sum(cl[k].generated[0] == ref[k].generated[0] for k in ref)
    full = sum(cl[k].generated == ref[k].generated for k in ref)
    print(f"[cluster] first tokens identical {first}/{len(ref)}; "
          f"identical full streams {full}/{len(ref)} "
          f"({full / len(ref):.3f})")
    check(first == len(ref), "first tokens must match the one-replica run")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.serve import setup_compile_cache
    cache_dir = setup_compile_cache(ROOT)

    import jax

    from repro.configs import get_config
    dev = jax.devices()[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(jax.devices())}; jax {jax.__version__}; "
          f"compile cache {cache_dir}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if dev.device_kind not in V5E_KINDS:
        print(f"chip_smoke: {dev.device_kind!r} is not a TPU v5e; the "
              "engine's cost profile is TPU_V5E", file=sys.stderr)
        return 1
    cfg = get_config("granite-3-2b")
    print(f"model: {cfg.arch_id} ({cfg.source}), {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
          f"vocab {cfg.vocab_size}, {cfg.dtype}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(cfg, args.seed)
    else:
        one_chip(cfg, dev, args.seed)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}; "
          f"total wall clock {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
