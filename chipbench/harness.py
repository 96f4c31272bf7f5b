"""One run of one cell: build the program's engine on one chip, warm every
shape the seed's traffic will use, serve open-loop traffic on the wall
clock, and keep records of every step and every token.

The system under test is the program's `LayerKVEngine` driven through its
`ServingSession.step()`. The harness only submits requests when they fall
due, reads each handle's new tokens after every step, and stamps them
with `time.perf_counter()`; no time here comes from the engine's virtual
clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from common import ROOT, model_dims
from traffic.gen import TrafficRequest, generate
from weights import make_weights

sys.path.insert(0, str(ROOT / "src"))


def _bucket(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclasses.dataclass
class ReqRec:
    tr: TrafficRequest
    due: float                      # absolute perf_counter time
    handle: object = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    prefill_step_start: Optional[float] = None
    submit_lag: float = 0.0

    @property
    def ttft(self) -> Optional[float]:
        return self.times[0] - self.due if self.times else None


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    prefill_lens: List[int]         # prompts whose prefill ran this step
    decode_ctxs: List[int]          # cached tokens of each decoded request
    kv_used: float                  # device pool share in use after it
    moves: int                      # offload/reload ledger entries so far


@dataclasses.dataclass
class CompileLog:
    """Backend compiles and cache loads, from JAX's monitoring events."""
    events: List[tuple] = dataclasses.field(default_factory=list)

    def listener(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), secs))

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


@dataclasses.dataclass
class RunData:
    """What the metric readers see."""
    cell: dict
    dims: dict
    peaks: dict
    reqs: List[ReqRec]
    steps: List[StepRec]
    w0: float
    w1: float
    setup_s: float
    compiles: CompileLog
    trace: object = None            # trace.TraceSummary of the window

    def counted(self) -> List[ReqRec]:
        return [r for r in self.reqs if r.tr.in_window]

    def window_steps(self) -> List[StepRec]:
        return [s for s in self.steps if s.t0 >= self.w0 and s.t1 <= self.w1]

    def gaps(self) -> List[float]:
        out = []
        for r in self.reqs:
            for a, b in zip(r.times, r.times[1:]):
                if self.w0 <= b <= self.w1:
                    out.append(b - a)
        return out


# ------------------------------------------------------------------ engine
def program_config(cfile: dict):
    """The program's ModelConfig with the sizes of the configuration file."""
    from repro.configs import get_config
    d = model_dims(cfile)
    base = get_config(cfile["program"]["arch"])
    return dataclasses.replace(
        base, n_layers=d["n_layers"], d_model=d["d_model"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        head_dim=d["head_dim"], d_ff=d["d_ff"], vocab_size=d["vocab_size"],
        qkv_bias=d["qkv_bias"], tie_embeddings=d["tie_embeddings"],
        rope_theta=d["rope_theta"], dtype=cfile["program"]["dtype"],
        max_seq_len=cfile["program"]["max_seq_len"])


def _prefill_working_bytes(ex, cfg, pad: int) -> int:
    """Temporaries and outputs of the exclusive prefill at `pad` tokens,
    as the compiler reports them."""
    batch = {"tokens": jax.ShapeDtypeStruct((1, pad), jnp.int32),
             "prompt_len": jax.ShapeDtypeStruct((1,), jnp.int32)}
    cache = jax.eval_shape(lambda: ex.model.init_cache(1, pad, cfg.dtype))
    ma = ex._prefill_fn.lower(ex.params, batch, cache).compile() \
        .memory_analysis()
    return int(ma.temp_size_in_bytes + ma.output_size_in_bytes)


def _decode_extra_bytes(ex, cfg, nblocks: int, rb: int, maxb: int) -> int:
    """Memory a decode step of batch bucket `rb` and table width `maxb`
    needs beyond its inputs, over a device pool of `nblocks` blocks, as
    the compiler reports it (temporaries, and outputs not written in
    place of a donated input). Raises the compiler's RESOURCE_EXHAUSTED
    where the step cannot fit beside its inputs at all."""
    i32 = jnp.int32
    pool = jax.ShapeDtypeStruct(
        (nblocks + 1,) + ex.device_pool.shape[1:], ex.device_pool.dtype)
    ma = ex._decode_fn.lower(
        ex.params, jax.ShapeDtypeStruct((rb,), i32),
        jax.ShapeDtypeStruct((cfg.n_layers, rb, maxb), i32),
        jax.ShapeDtypeStruct((rb,), i32), pool).compile().memory_analysis()
    return int(ma.temp_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes)


def pool_sizes(cfile: dict, cfg, params, max_pad: int, max_blocks: int,
               device) -> dict:
    """Size the KV pools as a deployment would. The budget is what HBM
    holds after the weights, less a margin. Beside the pools must fit the
    largest working memory of the served programs: the exclusive prefill
    at the largest prompt, or the decode step, whose temporaries grow
    with the device pool (the compiler copies the pool inside the step).
    The decode step's growth is read from the compiler at a quarter of
    the naive pool; the pool chosen from it is then compiled at the
    widest decode signature and shrunk while the compiler refuses it or
    reports more than fits beside the pools. On a TPU v5e the compiler's
    temporaries here read about 1.5x the scratch the runtime reserves
    for the step (`peak_bytes_reserved`), so the rule errs on the side of
    a smaller pool (PERF.md, section 4).
    The host tier, on HBM too, takes `host_share` of the pools."""
    from repro.serving.executor import PagedExecutor
    pools = cfile["pools"]
    bs = cfile["serve"]["block_size"]
    share = pools["host_share"]
    ex = PagedExecutor(cfg, params, 1, 1, bs, device=device)
    block_bytes = int(ex.device_pool[0].nbytes)
    prefill = _prefill_working_bytes(ex, cfg, max_pad)
    rb = _bucket(cfile["serve"]["max_batch_size"])
    maxb = _round_up(max_blocks, 8)
    ms = device.memory_stats() or {}
    limit = ms.get("bytes_limit", 0)
    budget = limit - ms.get("bytes_in_use", 0) \
        - pools["margin_frac"] * limit
    per_dev = block_bytes / (1.0 - share)   # a device block and its host
    probe = int(budget / per_dev / 4)
    per_block = _decode_extra_bytes(ex, cfg, probe, rb, maxb) / probe
    dev = int(min((budget - prefill) / per_dev,
                  budget / (per_dev + per_block)))
    while True:
        try:
            extra = _decode_extra_bytes(ex, cfg, dev, rb, maxb)
            if extra + dev * per_dev <= budget or dev <= probe:
                break
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e) or dev <= probe:
                raise
        dev = max(int(dev * 0.9), probe)
    ex.params = None        # jit caches keep the executor; not its arrays
    host = int(dev * share / (1.0 - share))
    return {"device": dev, "host": host, "block_bytes": block_bytes,
            "bytes_in_use": ms.get("bytes_in_use", 0),
            "prefill_working": prefill, "decode_extra_per_block": per_block,
            "decode_extra": extra}


def build_engine(cfile: dict, seed: int, device, max_pad: int,
                 max_blocks: int):
    from repro.models import build_model
    from repro.serving.engine import LayerKVEngine
    from repro.serving.scheduler import ServeConfig
    cfg = program_config(cfile)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    weights = make_weights(shapes, seed, cfg.vocab_size, device)
    jax.block_until_ready(weights)
    sizes = pool_sizes(cfile, cfg, weights, max_pad, max_blocks, device)
    sc = ServeConfig(num_device_blocks=sizes["device"],
                     num_host_blocks=sizes["host"],
                     **cfile["serve"]).validate()
    eng = LayerKVEngine(cfg, params=weights, ec=sc, device=device)
    jax.block_until_ready(eng.ex.device_pool)
    return eng, weights, sizes


# ------------------------------------------------------------------ warm-up
def _decode_grid(eng, reqs: List[TrafficRequest]) -> list:
    """Every (Rb, MAXB) decode signature the window can reach. Batch
    buckets run from 1 to the largest batch that both the admission cap
    and the device pool allow (every decoded request holds all its
    layers on the device, at least its prompt in each); table widths run,
    in the executor's 8-block steps, from the smallest prompt to the
    largest context any request reaches."""
    bs = eng.ec.block_size
    cap = eng.ec.max_tokens_per_request
    least = min(-(-len(r.prompt) // bs) for r in reqs)
    most = max(-(-(len(r.prompt) + min(r.output_len, cap)) // bs)
               for r in reqs)
    fits = max(eng.ex.num_device_blocks // (eng.L * least), 1)
    hi_rb = _bucket(min(eng.ec.max_batch_size, fits))
    rbs = [b for b in (2 ** i for i in range(12)) if b <= hi_rb]
    maxbs = list(range(_round_up(least, 8), _round_up(most, 8) + 1, 8))
    return [(rb, mb) for rb in rbs for mb in maxbs]


def warm_up(eng, cfile: dict, reqs: List[TrafficRequest]) -> dict:
    """Compile (or load from the persistent cache) every program the
    window can call by running each once."""
    ex = eng.ex
    L = eng.L
    bs = eng.ec.block_size
    trash = ex.num_device_blocks
    prompt_blocks = sorted({-(-len(r.prompt) // bs) for r in reqs})
    pads = sorted({_bucket(nb * bs, 16) for nb in prompt_blocks})
    grid = _decode_grid(eng, reqs)
    hi_rb = max(rb for rb, _ in grid)

    # one call per program compiles it (or loads it from the persistent
    # cache) together with the eager slices the engine applies to its
    # outputs. Compiling on several threads at once crashed the TPU
    # compiler (stack overflow, my chip runs): one at a time.
    for pad in pads:
        nbs = [nb for nb in prompt_blocks if _bucket(nb * bs, 16) == pad]
        _, k, v = ex.prefill([1] * (nbs[-1] * bs), pad)
        ks = [k[l] for l in range(L)]
        vs = [v[l] for l in range(L)]
        for nb in nbs:
            for tier in ("device", "host"):
                ex.write_layer(tier, [ex.num_device_blocks if tier ==
                                      "device" else ex.num_host_blocks] * nb,
                               ks[0], vs[0])
        del k, v, ks, vs
    max_nb = -(-max(len(r.prompt) + min(r.output_len,
                                        eng.ec.max_tokens_per_request)
                    for r in reqs) // bs)
    for n in range(1, max_nb + 1):
        ex.copy_blocks("device", "host", [trash] * n,
                       [ex.num_host_blocks] * n)
        ex.copy_blocks("host", "device", [ex.num_host_blocks] * n,
                       [trash] * n)
    by_rb: Dict[int, int] = {}
    for rb, mb in grid:
        tab = np.full((L, rb, mb), trash, np.int32)
        ex.decode([0] * rb, tab, [0] * rb)
        by_rb.setdefault(rb, mb)
    for r in range(1, min(hi_rb, eng.ec.max_batch_size) + 1):
        rb = _bucket(r)
        if rb in by_rb:
            ex.decode([0] * r, np.full((L, r, by_rb[rb]), trash, np.int32),
                      [0] * r)
    jax.block_until_ready(ex.device_pool)
    return {"prefill_pads": pads, "decode": grid,
            "write_blocks": prompt_blocks, "copy_blocks_max": max_nb}


def watch_reloads(eng) -> set:
    """The ids of the requests whose offloaded layers the engine brings
    back to the device (`_ensure_device`, which copies them host to
    device), gathered as the run goes; `correct` samples from them."""
    from repro.core import HOST
    seen: set = set()
    ensure = eng._ensure_device

    def wrapped(r):
        before = len(eng.bm.layers_on(r.rid, HOST))
        ok = ensure(r)
        if len(eng.bm.layers_on(r.rid, HOST)) < before:
            seen.add(r.rid)
        return ok
    eng._ensure_device = wrapped
    return seen


# ------------------------------------------------------------------- spans
@contextlib.contextmanager
def host_spans(eng):
    """Wrap the engine's calls in profiler spans with stable names (only
    for a traced run): admission, batch selection and each executor call."""
    from jax.profiler import TraceAnnotation

    def wrap(obj, attr, name):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with TraceAnnotation(name):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)
        return obj, attr

    done = [wrap(eng.core, "admit_waiting", "sched.admit_waiting"),
            wrap(eng, "_select_runnable", "sched.select_runnable"),
            wrap(eng.ex, "prefill", "exec.prefill"),
            wrap(eng.ex, "write_layer", "exec.write_layer"),
            wrap(eng.ex, "copy_blocks", "exec.copy_blocks"),
            wrap(eng.ex, "decode", "exec.decode")]
    try:
        yield
    finally:
        for obj, attr in done:
            delattr(obj, attr)


# ------------------------------------------------------------------ serving
def serve(eng, recs: List[ReqRec], w0: float, w1: float, tail_s: float,
          on_open=None, on_close=None) -> List[StepRec]:
    """Open-loop serving on the wall clock until every request due in the
    window [w0, w1) has its first token or the tail runs out. `on_open`
    and `on_close` run at the first step boundary after w0 and w1 (the
    traced run starts and stops the profiler there)."""
    from repro.core import DEVICE
    from repro.serving.request import Request
    from repro.serving.session import ServingSession
    session = ServingSession(eng)
    pending = deque(sorted(recs, key=lambda r: r.due))
    live: List[ReqRec] = []
    steps: List[StepRec] = []
    ndb = eng.ex.num_device_blocks
    cutoff = w1 + tail_s
    counted = [r for r in recs if r.tr.in_window]
    opened = closed = False
    clock = time.perf_counter
    while True:
        now = clock()
        if not opened and now >= w0:
            opened = True
            if on_open:
                on_open()
        while pending and pending[0].due <= now and now < w1:
            r = pending.popleft()
            req = Request(rid=r.tr.rid, prompt_len=len(r.tr.prompt),
                          output_len=r.tr.output_len, prompt=r.tr.prompt)
            r.handle = session.submit(req)
            r.submit_lag = now - r.due
            live.append(r)
        if now >= w1:
            pending.clear()
            if not closed:
                closed = True
                if on_close:
                    on_close()
            if now >= cutoff or all(r.times for r in counted):
                break
        t0 = clock()
        busy = session.step()
        t1 = clock()
        if not busy:
            nxt = pending[0].due if pending else w1
            time.sleep(max(0.0, min(nxt - clock(), 0.002)))
            continue
        prefills, ctxs = [], []
        still = []
        for r in live:
            new = r.handle.take_new()
            if new:
                if not r.times:
                    r.prefill_step_start = t0
                    prefills.append(len(r.tr.prompt))
                else:
                    ctxs.append(len(r.tr.prompt) + len(r.tokens) - 1)
                r.tokens.extend(new)
                r.times.extend([t1] * len(new))
            if not r.handle.done:
                still.append(r)
        live = still
        steps.append(StepRec(t0, t1, prefills, ctxs,
                             1.0 - eng.bm.num_free(DEVICE) / max(ndb, 1),
                             len(eng.off.ledger.log)))
    return steps


def make_records(reqs: List[TrafficRequest], origin: float) -> List[ReqRec]:
    return [ReqRec(tr=r, due=origin + r.due) for r in reqs]


def free_engine(eng) -> None:
    """Drop the engine's pools (the weights stay, for the reference). The
    jit caches keep the executor object alive, so its arrays are dropped
    by hand."""
    eng.ex.device_pool = None
    eng.ex.host_pool = None
    eng.ex.params = None
    gc.collect()


def traffic_for(cfile: dict, mix: dict, seed: int, seconds: float):
    d = model_dims(cfile)
    reqs = generate(mix, seed, seconds, d["vocab_size"])
    limit = d["context_limit"]
    for r in reqs:
        if len(r.prompt) + r.output_len > limit:
            raise SystemExit(
                f"traffic exceeds the context limit {limit}: {r.rid}")
    return reqs


def max_blocks(cfile: dict, reqs) -> int:
    """Blocks per layer of the longest context any request reaches."""
    bs = cfile["serve"]["block_size"]
    cap = cfile["serve"]["max_tokens_per_request"]
    return max(-(-(len(r.prompt) + min(r.output_len, cap)) // bs)
               for r in reqs)


def max_pad(cfile: dict, reqs) -> int:
    bs = cfile["serve"]["block_size"]
    return max(_bucket(-(-len(r.prompt) // bs) * bs, 16) for r in reqs)

