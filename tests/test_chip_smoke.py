"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, the
Pallas interpret switch is strict, the compile cache lands where it
should, and the script's phases pass at a tiny size on the CPU
(interpret-mode kernels).  The platform check is bypassed only here, by
calling the phases directly instead of ``main``."""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs.base import DKSBenchConfig
from repro.kernels import interpret_mode
from repro.launch import DEFAULT_COMPILE_CACHE, enable_compile_cache

ROOT = Path(__file__).resolve().parent.parent
# Small enough for interpret-mode kernels, large enough that the replay
# (trace seed 6) has finite exact answers at both m and two distinct
# answer trees.
TINY = dict(name="tiny", n_nodes=2000, n_edges=2200, vocab=200)
SEED = 6


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("platform,expect", [("cpu", True), ("tpu", False)])
def test_interpret_mode_by_platform(monkeypatch, platform, expect):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert interpret_mode() is expect


def test_interpret_mode_rejects_other_platforms(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        interpret_mode()


def test_compile_cache_prefers_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_inside_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(DEFAULT_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(
            DEFAULT_COMPILE_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    assert DEFAULT_COMPILE_CACHE.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{DEFAULT_COMPILE_CACHE.name}/" in ignored


def test_chip_smoke_refuses_without_tpu(capsys):
    smoke = _load_smoke()
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert "no TPU found" in str(exc.value.code)
    assert capsys.readouterr().out == ""   # and prints no result line


def test_chip_smoke_serve_phase_tiny():
    smoke = _load_smoke()
    out = smoke.serve_phase(DKSBenchConfig(**TINY), SEED, lanes=2)
    assert out["compiled"] is None          # no sizing when lanes given
    assert len(out["served"]) == 20
    assert all(r is not None for r in out["served"])


def test_chip_smoke_four_chip_phase_on_four_cpu_devices():
    prog = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.configs.base import DKSBenchConfig
        smoke.four_chip_phase(DKSBenchConfig(**{TINY!r}), {SEED})
        print("FOUR_CHIP_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600, env=env)
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    assert "packed graph shards on 4 distinct devices" in res.stdout
    assert "FOUR_CHIP_OK" in res.stdout
