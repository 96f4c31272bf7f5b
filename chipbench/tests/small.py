"""A cell small enough for the CPU: the harness's own tests drive every
step of a run after the look for a chip through it (`run.run_cell`),
with pools of a fixed size in place of the sizing from HBM."""
from __future__ import annotations

import copy

import jax

import common
import harness
import run

TINY_CONFIG = {
    "name": "tiny",
    "num_hidden_layers": 2,
    "hidden_size": 64,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "intermediate_size": 128,
    "vocab_size": 500,
    "max_position_embeddings": 256,
    "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True,
    "attention_bias": False,
    "program": {"arch": "granite-3-2b", "dtype": "float32",
                "max_seq_len": 256},
    "serve": {"policy": "layerkv", "slo_aware": True, "chunked": False,
              "prefix_cache": False, "block_size": 8, "max_batch_size": 4,
              "max_tokens_per_request": 4096},
    "pools": {"host_share": 0.5, "margin_frac": 0.0},
    "correct": {"max_logit_gap": 1e-3, "sample_tokens": 40,
                "max_requests": 4, "min_tokens_compared": 10},
}

TINY_TRAFFIC = {
    "rate_rps": 8.0, "warm_in_s": 0.3, "tail_s": 20.0, "sizes_seed": 0,
    "prompt": {"dist": "uniform", "min": 8, "max": 40},
    "output": {"dist": "uniform", "min": 4, "max": 10},
}

# device and host blocks of the tiny cell: few enough that admission
# offloads layers and decode brings them back
POOL_BLOCKS = {"device": 12, "host": 24, "block_bytes": 0}

CELL = {"name": "tiny.small", "config": "tiny", "traffic": "small",
        "chips": 1, "why": "CPU test"}


def fixed_pools(monkeypatch) -> None:
    monkeypatch.setattr(harness, "pool_sizes",
                        lambda *a, **k: dict(POOL_BLOCKS))


def tiny_inputs(seed: int, monkeypatch, seconds: float = 1.5) -> tuple:
    """What `run.run_cell` (and `control.read_seed`) take, for the tiny
    cell on the CPU: args, bench, cell, config, mix, devices, peaks."""
    fixed_pools(monkeypatch)
    bench = copy.deepcopy(common.benchmark())
    bench["workloads"].append(dict(CELL))
    args = run.parse(["--workload", CELL["name"], "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    return (args, bench, dict(CELL), copy.deepcopy(TINY_CONFIG),
            copy.deepcopy(TINY_TRAFFIC), jax.devices(),
            common.peaks("TPU v5 lite"))


def tiny_run(seed: int, monkeypatch, seconds: float = 1.5):
    """A whole run of the tiny cell on the CPU: (result, kept records)."""
    return run.run_cell(*tiny_inputs(seed, monkeypatch, seconds),
                        run.process_start())
