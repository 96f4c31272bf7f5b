"""`chip_smoke.py` off the chip: it refuses to run without a TPU (or
without the repo around it) and prints no result, and its phases and
logits check run end to end on the CPU at a tiny width with the Pallas
paged kernels in interpret mode."""
import dataclasses
import functools
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.kernels import paged_prefill as pp
from repro.models import build_model

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(script, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_refuses_without_tpu():
    p = _run(ROOT / "chip_smoke.py", ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not _printed_result(p.stdout)


def test_refuses_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    p = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke imported with `ops` steered onto the Pallas kernels in
    interpret mode, as the chip would run them."""
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    monkeypatch.setattr(ops, "paged_impl", lambda: "pallas")
    monkeypatch.setattr(pa, "paged_attention_pallas", functools.partial(
        pa.paged_attention_pallas, interpret=True))
    monkeypatch.setattr(pp, "paged_prefill_pallas", functools.partial(
        pp.paged_prefill_pallas, interpret=True))
    return chip_smoke


@pytest.mark.slow
def test_phases_and_logits_check_on_cpu(smoke):
    """granite-3-2b's block at a tiny width and 2 layers, bf16: phase (a)
    offloads and reloads, phase (b) runs every paged kernel variant, and
    the served logits meet the float32 check."""
    cfg = dataclasses.replace(
        get_config("granite-3-2b"), n_layers=2, d_model=128, n_heads=8,
        n_kv_heads=2, d_ff=256, vocab_size=512)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    dev = jax.devices()[0]
    smoke.check_logits(cfg, params, dev, seed=0)
    smoke.phase_exclusive(cfg, params, dev, seed=0)
    smoke.phase_fused(cfg, params, dev, seed=0)


@pytest.fixture
def cache_config():
    """Restore JAX's compilation-cache directory after the test."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


def test_compile_cache_dir_env_wins(monkeypatch, tmp_path, cache_config):
    from repro.launch.serve import setup_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert setup_compile_cache(ROOT) == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    """Importing the entry points sets nothing; calling the setup picks
    the fixed, gitignored `.jax_cache/` at the checkout root."""
    monkeypatch.syspath_prepend(str(ROOT))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    import chip_smoke  # noqa: F401
    from repro.launch.serve import setup_compile_cache
    assert jax.config.jax_compilation_cache_dir == before
    path = setup_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_four_replicas_on_virtual_devices():
    """The `--chips 4` path on four virtual CPU devices: each replica on
    its own device, every request served, first tokens equal to one
    replica's (tiny width, ref kernels)."""
    code = (
        "import dataclasses, chip_smoke\n"
        "from repro.configs import get_config\n"
        "cfg = dataclasses.replace(get_config('granite-3-2b'), n_layers=2,"
        " d_model=128, n_heads=8, n_kv_heads=2, d_ff=256, vocab_size=512)\n"
        "chip_smoke.four_chips(cfg, 0)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    for i in range(4):
        assert f"replica {i}: params on ['TFRT_CPU_{i}']" in p.stdout
    assert "first tokens identical 8/8" in p.stdout
