"""The chip entry points refuse to report success without a chip.

`chip_smoke.py` and `bench.py` must fail, and print no success or metric
line, when JAX finds no TPU; the coding path's backend queries raise
instead of answering "cpu" or "one device"; and the compile cache lands
where `JAX_COMPILATION_CACHE_DIR` says, or at a fixed path in the
checkout.  The smoke script's own phases are rehearsed here on the CPU
at a tiny size.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from ceph_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(tmp_path) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax-cache")
    env.pop("PYTHONPATH", None)
    return env


def _run(args, cwd, env, timeout=240):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=timeout,
    )


@pytest.mark.parametrize("extra", [[], ["--chips", "4"]])
def test_chip_smoke_fails_without_a_tpu(tmp_path, extra):
    proc = _run(["chip_smoke.py", *extra], REPO, _cpu_env(tmp_path))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "platform is tpu" in proc.stderr


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(["chip_smoke.py"], str(tmp_path), _cpu_env(tmp_path))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_bench_fails_without_a_tpu(tmp_path):
    proc = _run(["bench.py"], REPO, _cpu_env(tmp_path))
    assert proc.returncode == 3, proc.stderr
    assert "metric" not in proc.stdout


def test_smoke_phases_rehearse_on_cpu(tmp_path):
    """codec + cluster phases at a tiny size, in a fresh process so the
    fallback and guard checks see only this run."""
    script = (
        "import chip_smoke as cs\n"
        "from ceph_tpu.utils.compile_cache import CompileClock\n"
        "clock = CompileClock()\n"
        "cs.phase_codec(4, 4096, clock, require_pallas=False)\n"
        "cs.phase_cluster(3, 1, 4 << 15, 4, clock, require_pallas=False)\n"
    )
    proc = _run(["-c", script], REPO, _cpu_env(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    phases = [json.loads(line) for line in proc.stdout.splitlines()
              if line.startswith("{")]
    assert [p["phase"] for p in phases] == ["codec", "cluster"]
    cluster = phases[1]["checked"]
    assert any("0 inconsistencies" in c for c in cluster), cluster
    assert any("recovered to clean" in c for c in cluster), cluster


def test_on_tpu_raises_when_backend_query_fails(monkeypatch):
    from ceph_tpu.codec import matrix_codec

    def broken():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(matrix_codec, "_PLATFORM", None)
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend init failed"):
        matrix_codec._on_tpu()


def test_visible_devices_raises_when_backend_query_fails(monkeypatch):
    from ceph_tpu.parallel import dispatch

    def broken():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(dispatch, "_visible", None)
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend init failed"):
        dispatch._visible_devices()
    with pytest.raises(RuntimeError, match="backend init failed"):
        dispatch.shard_mesh(dispatch.DEFAULT_MIN_BATCH)


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_follows_the_variable(monkeypatch, tmp_path,
                                            restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: no directory is set in code
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
