"""Bring-up pieces that need no chip (ISSUE 22): where the compile cache
goes, the native build's missing-compiler error, and ``chip_smoke.py``'s
contract off the chip (fails without a TPU; rehearses on the CPU)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


# ------------------------------------------------------------ compile cache
def test_cache_dir_is_the_environments_when_set(monkeypatch, tmp_path):
    from horovod_tpu.common import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_cache_dir_is_one_fixed_path_in_the_checkout(monkeypatch):
    """Unset: ``.jax_cache/`` beside the package — git-ignored, and never
    built from a temporary name, a pid or the time (a cache that moves
    never hits)."""
    from horovod_tpu.common import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir() == compile_cache.cache_dir()
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_enable_points_jax_at_the_fixed_path(monkeypatch):
    import jax
    from horovod_tpu.common import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()


def test_enable_sets_nothing_where_the_environment_placed_the_cache(
        tmp_path):
    """A fresh interpreter with the variable set: jax reads it itself, and
    ``enable()`` leaves the config alone — the cache lands there and
    nowhere else."""
    code = ("import jax\n"
            "from horovod_tpu.common import compile_cache as c\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "assert c.enable() == before == c.cache_dir(), (before,)\n"
            "assert jax.config.jax_compilation_cache_dir == before\n"
            "print(before)\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)


def test_hvd_init_leaves_cpu_runs_without_a_persistent_cache(hvd):
    """Tier-1 behaviour is unchanged: on the CPU ``hvd.init()`` places no
    cache (only an accelerator's long compiles are kept)."""
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        pytest.skip("the environment placed a cache itself")
    assert jax.config.jax_compilation_cache_dir is None


# ------------------------------------------------------------- native build
def test_native_build_without_a_compiler_says_so(monkeypatch, tmp_path):
    """``csrc/coordinator.cc`` is built on first use into the git-ignored
    ``horovod_tpu/lib/``: from a tree without it, a missing ``g++`` must
    be an error that names the compiler."""
    from horovod_tpu.common import native
    monkeypatch.setattr(native, "_OUT_DIR", str(tmp_path / "lib"))
    monkeypatch.setenv("PATH", str(tmp_path))
    with pytest.raises(RuntimeError, match=r"no C\+\+ compiler.*g\+\+"):
        native._build()


def test_native_build_failure_carries_the_compilers_output(monkeypatch,
                                                           tmp_path):
    from horovod_tpu.common import native
    bad = tmp_path / "bad.cc"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_OUT_DIR", str(tmp_path / "lib"))
    monkeypatch.setattr(native, "_SRC", str(bad))
    with pytest.raises(RuntimeError, match="g\\+\\+ exit"):
        native._build()


def test_native_build_from_a_tree_without_lib(monkeypatch, tmp_path):
    """The real source builds into an empty output directory."""
    from horovod_tpu.common import native
    monkeypatch.setattr(native, "_OUT_DIR", str(tmp_path / "lib"))
    out = native._build()
    assert os.path.exists(out) and out.startswith(str(tmp_path))


# --------------------------------------------------------------- chip_smoke
def _smoke(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_fails_without_a_tpu():
    """No TPU and not told to rehearse: non-zero exit, no result line."""
    r = _smoke()
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the script cannot import the program and must fail."""
    import shutil
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse"],
                       env=env, cwd=tmp_path, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_chip_smoke_rehearsal_runs_every_phase_on_the_cpu():
    """The smoke's control flow at tiny sizes: every default phase passes
    and the last line names the device for what it is (the CPU)."""
    r = _smoke("--rehearse")
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    phases = [l["phase"] for l in lines if l.get("ok") and "phase" in l]
    assert phases == ["device", "engine", "resnet50_spmd", "resnet50_eager",
                      "flash_llama"]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
