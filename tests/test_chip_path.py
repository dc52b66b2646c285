"""What the chip run depends on, checked on the CPU: importing the package
leaves the device alone, the compile cache goes where it is told, and
``chip_smoke.py`` refuses to run without a TPU but runs its one-chip phases
end to end at a tiny size."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code_or_script, env_extra, *args):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, *code_or_script, *args], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=ROOT)


def test_import_leaves_jax_backend_uninitialized():
    # a spawn worker or a benchmark child imports the package while its
    # parent may hold the chip: the import itself must take no device
    r = _run(["-c", "import repro.core, repro.runtime\n"
                    "from jax._src import xla_bridge as xb\n"
                    "print(xb.backends_are_initialized())"], {})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["False"]


def test_compile_cache_follows_env_then_fixed_path(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache as cc
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert calls == []                       # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = cc.enable_compile_cache()
    assert path == os.path.realpath(os.path.join(ROOT, ".jax_cache"))
    assert calls == [("jax_compilation_cache_dir", path)]


def test_chip_smoke_refuses_without_tpu():
    r = _run(["chip_smoke.py"], {})
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_chip_smoke_one_chip_phases_at_tiny_size():
    # interpret-mode kernels on the CPU: no tpu_custom_call to find, and a
    # store and cache scaled down; everything else is the chip's path
    import chip_smoke
    chip_smoke.one_chip(num_chunks=2, table_bytes=3e6, min_store=0,
                        cache_bytes=64 << 10, bottom=(32, 128),
                        top=(32, 1), want_kernels=False)
