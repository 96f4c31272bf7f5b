"""Paths and loaders shared by the harness: the benchmark file, the
configuration and traffic files, the peaks table and the metric readers,
each found by the name `BENCHMARK.json` gives it."""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_file(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["kinds"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def metric_reader(name: str):
    """The `value(run)` function of `chipbench/metrics/<name>.py`."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.value


# published configuration keys -> the names the harness and the reference
# use; the first key present wins
_DIM_KEYS = {
    "n_layers": ("num_hidden_layers", "num_layers"),
    "d_model": ("hidden_size",),
    "n_heads": ("num_attention_heads",),
    "n_kv_heads": ("num_key_value_heads", "multi_query_group_num"),
    "head_dim": ("head_dim", "kv_channels"),
    "d_ff": ("intermediate_size", "ffn_hidden_size"),
    "vocab_size": ("vocab_size", "padded_vocab_size"),
    "norm_eps": ("rms_norm_eps", "layernorm_epsilon"),
    "rope_theta": ("rope_theta",),
    "partial_rotary_factor": ("partial_rotary_factor",),
    "tie_embeddings": ("tie_word_embeddings",),
    "context_limit": ("max_position_embeddings", "seq_length"),
    "qkv_bias": ("attention_bias", "add_qkv_bias"),
}


def model_dims(cfg: dict) -> dict:
    """Canonical sizes of a configuration file, with its `as_run` values
    (where the program departs from the published ones) laid over it."""
    cfg = {**cfg, **cfg.get("as_run", {})}
    d = {}
    for k, keys in _DIM_KEYS.items():
        for key in keys:
            if key in cfg:
                d[k] = cfg[key]
                break
    d.setdefault("head_dim", d["d_model"] // d["n_heads"])
    d.setdefault("partial_rotary_factor", 1.0)
    d.setdefault("rope_theta", 10000.0)
    d.setdefault("tie_embeddings", False)
    d.setdefault("qkv_bias", False)
    d["padded_vocab"] = -(-d["vocab_size"] // 256) * 256
    d["tie_embeddings"] = bool(d["tie_embeddings"])
    d["qkv_bias"] = bool(d["qkv_bias"])
    return d
