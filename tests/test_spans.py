"""Profiler spans (`repro.obs.spans`): a CPU engine run under the
profiler records every layer boundary's span with its stats, on a pool
small enough that layers offload, get evicted and reload; the served
jitted programs carry stable names; spans never steer."""
from __future__ import annotations

import dataclasses
import glob
import pathlib
import random
import re
from collections import defaultdict

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.obs import SPAN_NAMES, span
from repro.obs.trace import ATTRIBUTION_CAUSES
from repro.serving.engine import LayerKVEngine
from repro.serving.executor import PagedExecutor
from repro.serving.request import Request
from repro.serving.scheduler import ServeConfig

SRC = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"

# the spans the benchmark's harness wraps around the engine from outside
HARNESS_SPANS = ("sched.admit_waiting", "sched.select_runnable",
                 "exec.prefill", "exec.write_layer", "exec.copy_blocks",
                 "exec.decode")

# span -> the stats it carries (a span opened in the engine and in the
# executor under one name carries the union)
STATS = {
    "sched.step": {"step"},
    "sched.admit": {"waiting", "admitted", "stop_gate"},
    "sched.admit.budget": {"budget"},
    "sched.admit.alloc": {"rid", "retained", "offloaded"},
    "sched.prefill": {"rid", "tokens"},
    "sched.select": {"rows"},
    "sched.kv.evict": {"rid", "layers"},
    "sched.kv.reload": {"rid", "layers"},
    "sched.retire": {"finished"},
    "exec.prefill.launch": {"tokens", "tokens_padded"},
    "exec.prefill.wait": set(),
    "exec.decode.prep": {"rows", "rows_padded", "slots", "slots_padded"},
    "exec.decode.launch": set(),
    "exec.decode.wait": set(),
    "exec.chunk.launch": {"tokens"},
    "exec.chunk.wait": set(),
    "exec.mixed.prep": {"chunks", "rows", "tokens_padded", "rows_padded"},
    "exec.mixed.launch": set(),
    "exec.mixed.wait": set(),
    "exec.kv.write": {"tier", "blocks", "bytes"},
    "exec.kv.copy": {"src", "dst", "blocks", "bytes"},
    "exec.kv.gather": {"tier", "blocks", "bytes"},
}

SCHED = ("sched.step", "sched.admit", "sched.admit.budget",
         "sched.admit.alloc", "sched.select", "sched.kv.evict",
         "sched.kv.reload", "sched.retire")
MODES = {
    # exclusive prefill: every span of the program's exclusive path
    "exclusive": (dict(), SCHED + (
        "sched.prefill", "exec.prefill.launch", "exec.prefill.wait",
        "exec.decode.prep", "exec.decode.launch", "exec.decode.wait",
        "exec.kv.write", "exec.kv.copy")),
    "chunked": (dict(chunked=True), SCHED + (
        "exec.chunk.launch", "exec.chunk.wait", "exec.kv.gather",
        "exec.decode.prep", "exec.decode.launch", "exec.decode.wait",
        "exec.kv.write", "exec.kv.copy")),
    "fused": (dict(chunked=True, fused=True), SCHED + (
        "exec.mixed.prep", "exec.mixed.launch", "exec.mixed.wait",
        "exec.kv.copy")),
}


def _engine(**kw):
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    # 16 device blocks of 8 tokens over 2 layers for five prompts of
    # 12-38 tokens, all due at once: layers offload, get evicted and
    # reload
    ec = ServeConfig.for_engine(policy="layerkv", num_device_blocks=16,
                                block_size=8, num_host_blocks=64, **kw)
    return LayerKVEngine(cfg, None, ec, rng=jax.random.PRNGKey(0))


def _requests(vocab):
    rng = random.Random(3)
    return [Request(rid=f"r{i}", prompt_len=n, output_len=10, arrival=0.0,
                    prompt=[rng.randrange(vocab) for _ in range(n)])
            for i, n in enumerate((30, 20, 38, 12, 26))]


def _profiled(eng, tmp_path):
    """Serve the requests under the profiler; returns (tokens by rid,
    the recorded program spans as (start, end, name, stats))."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        done = eng.run(_requests(eng.cfg.vocab_size))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name, dict(e.stats))
                             for e in line.events
                             if e.name.startswith(("sched.", "exec.")))
    return {r.rid: list(r.generated) for r in done}, spans


@pytest.mark.parametrize("mode", MODES, ids=list(MODES))
def test_engine_spans_and_stats_under_the_profiler(mode, tmp_path):
    kw, expected = MODES[mode]
    eng = _engine(**kw)
    tokens, spans = _profiled(eng, tmp_path)
    stats = defaultdict(list)
    for _, _, name, st in spans:
        stats[name].append(st)
    assert set(stats) <= set(SPAN_NAMES)
    assert set(expected) <= set(stats), sorted(set(expected) - set(stats))
    for name, seen in stats.items():
        assert set().union(*seen) == STATS[name], name
    # the pool was tight: layers offloaded, evicted and reloaded
    assert sum(s.get("offloaded", 0) for s in stats["sched.admit.alloc"])
    assert sum(s["layers"] for s in stats["sched.kv.evict"]) > 0
    assert sum(s["layers"] for s in stats["sched.kv.reload"]) > 0
    # bytes are the pool's own: blocks x one block's nbytes
    nbytes = eng.ex.device_pool.nbytes // eng.ex.device_pool.shape[0]
    assert eng.ex.block_nbytes == nbytes
    for st in stats["exec.kv.copy"]:
        assert st["bytes"] == st["blocks"] * nbytes > 0
    if mode == "exclusive":
        assert {s["tier"] for s in stats["exec.kv.write"]} == \
            {"device", "host"}
        for st in stats["exec.kv.write"]:
            assert st["bytes"] == st["blocks"] * nbytes > 0
    gates = {c for c in ATTRIBUTION_CAUSES if c.startswith("gate:")}
    assert {s["stop_gate"] for s in stats["sched.admit"]} <= \
        gates | {"none"}
    assert sum(s["admitted"] for s in stats["sched.admit"]) == 5
    steps = sorted((a, b) for a, b, n, _ in spans if n == "sched.step")
    assert [s["step"] for s in stats["sched.step"]] == \
        list(range(1, len(steps) + 1))
    # every other span nests inside one engine step
    for a, b, name, _ in spans:
        if name != "sched.step":
            assert any(s0 <= a and b <= s1 for s0, s1 in steps), name
    # spans observe, never steer: the same tokens with the profiler off
    assert tokens == {r.rid: list(r.generated)
                      for r in _engine(**kw).run(
                          _requests(eng.cfg.vocab_size))}


def test_span_is_a_no_op_without_the_profiler():
    with span("sched.step", step=1) as sp:
        sp.set_metadata(rows=3)
    assert not isinstance(sp, jax.profiler.TraceAnnotation)


def test_span_vocabulary_is_the_programs():
    """Every span the program opens is in `SPAN_NAMES` and every name
    there is opened somewhere; names keep the benchmark's prefixes,
    stay clear of the harness's own spans, and only host waits on a
    device result end in `.wait`."""
    opened = set()
    for path in SRC.rglob("*.py"):
        opened |= set(re.findall(r'(?<![\w.])span\(\s*"([^"]+)"',
                                 path.read_text()))
    assert opened == set(SPAN_NAMES)
    assert len(SPAN_NAMES) == len(set(SPAN_NAMES))
    assert all(n.startswith(("sched.", "exec.")) for n in SPAN_NAMES)
    assert not set(SPAN_NAMES) & set(HARNESS_SPANS)
    assert {n for n in SPAN_NAMES if n.endswith(".wait")} == {
        "exec.prefill.wait", "exec.decode.wait", "exec.chunk.wait",
        "exec.mixed.wait"}
    assert set(STATS) == set(SPAN_NAMES)


# ------------------------------------------------------ program names ----

def _lowered(program):
    """The lowering of one served jitted program at smoke size."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    P = PagedExecutor
    ex = P(cfg, None, 8, 8, 8)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    k = jnp.zeros((8, KV, hd))
    if program == "serve_prefill":
        batch = {"tokens": i32(1, 16), "prompt_len": i32(1)}
        return ex._prefill_fn.lower(ex.params, batch,
                                    ex.model.init_cache(1, 16, cfg.dtype))
    if program == "serve_decode":
        return ex._decode_fn.lower(ex.params, i32(2), i32(L, 2, 8), i32(2),
                                   ex.device_pool)
    if program == "kv_write.layer":
        return P._scatter_layer.lower(ex, ex.device_pool, i32(1), k, k)
    if program == "kv_write.slice":
        return P._scatter_slice.lower(ex, ex.device_pool, i32(8), i32(8),
                                      k, k)
    if program == "kv_copy.across":
        return P._copy_blocks.lower(ex, ex.device_pool, ex.host_pool,
                                    i32(1), i32(1))
    if program == "kv_copy.within":
        return P._copy_blocks_within.lower(ex, ex.device_pool, i32(1),
                                           i32(1))
    if program == "serve_chunk":
        buf = jnp.zeros((L, 16, KV, hd))
        return P._chunk_forward.lower(ex, ex.params, i32(4), buf, buf,
                                      jnp.int32(0), jnp.int32(4))
    assert program == "serve_mixed"
    Tc, Sc, Rb, Sb = 32, 1, 1, 2
    T = Tc + Rb
    return P._mixed_forward.lower(
        ex, ex.params, i32(T), i32(T), i32(T), i32(L, T), i32(L, T),
        i32(Tc), i32(Tc), i32(Sc), i32(L, Sc, 8),
        jnp.zeros((L, Sc), bool), i32(L, Rb, 8), i32(Rb), i32(Sb),
        jnp.zeros(Sb, bool), ex.device_pool, ex.host_pool, False)


@pytest.mark.parametrize("program", [
    "serve_prefill", "serve_decode", "kv_write.layer", "kv_write.slice",
    "kv_copy.across", "kv_copy.within", "serve_chunk", "serve_mixed"])
def test_served_programs_keep_stable_names(program):
    """The device trace's "XLA Modules" line names each program by its
    jit name; the benchmark reads `jit_serve_decode` there."""
    text = _lowered(program).as_text()
    name = program.split(".")[0]
    assert re.search(r"module @(\S+)", text).group(1) == f"jit_{name}"
