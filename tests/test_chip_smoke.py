"""chip_smoke.py rehearsed on the CPU: its phases at smoke widths (the
script itself refuses to run off the TPU, and that refusal is checked
too). The control flow, the sealed preemption, the fused restore and the
kernel checks run here exactly as on the chip; only the sizes differ."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_at_smoke_size(chip_smoke, monkeypatch, capsys):
    from repro.configs import smoke_config
    from repro.core import sealing
    # every parameter leaf takes the chunked device seal/unseal path
    monkeypatch.setattr(sealing, "CHUNK_BYTES", 1024)
    chip_smoke.one_chip(smoke_config("llama2-7b"), chip_smoke.Phases())
    out = capsys.readouterr().out
    assert "unsealed parameters bit-identical to the originals: True" in out
    assert "paged_attention_unseal" in out
    served = next(l for l in out.splitlines() if l.startswith("served "))
    assert "preemptions 0;" not in served
    assert "fused-restore pages 0 " not in served


def test_four_chip_phases_on_forced_cpu_devices(subproc):
    out = subproc(f"""
import importlib.util
from repro.configs import smoke_config
spec = importlib.util.spec_from_file_location("chip_smoke",
                                              r"{ROOT / 'chip_smoke.py'}")
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs.four_chips(smoke_config("llama2-7b"), cs.Phases())
print("FOUR-CHIP OK")
""", devices=4)
    assert "tokens identical to the single-device run: True" in out
    assert "FOUR-CHIP OK" in out


def test_refuses_to_run_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "elsewhere/jax-cache"])
def test_compile_cache_directory(monkeypatch, env_dir):
    """Without JAX_COMPILATION_CACHE_DIR the cache is the checkout's own
    .jax_cache; with it, JAX reads the variable and nothing is set."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.cache import use_compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = use_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
    if env_dir is None:
        assert got == now == str(ROOT / ".jax_cache")
    else:
        assert got == env_dir and now == was
