"""Entry-point plumbing: compile-cache placement, the chip smoke's
refusal to run without a TPU, and one device per front-door replica."""
import argparse
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the compile-cache settings enable_compile_cache() sets."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(from_env, cache_config, monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins; unset, the cache sits at the
    fixed in-checkout path, whatever the working directory."""
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(tmp_path)
        want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_chip_smoke_refuses_cpu():
    """No TPU, no result: the smoke exits non-zero and prints no
    {"ok": ...} line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    assert "no TPU" in run.stderr


def test_frontdoor_replicas_get_their_own_device(tp_mesh):
    """--replicas R at tp=1 with R devices: one device per replica
    (they used to share device 0)."""
    from repro.launch.serve import build_frontdoor
    from repro.models import transformer as T
    from repro.models.registry import get_config

    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    args = argparse.Namespace(
        replicas=4, tp=1, profile=None, exec_spec=None, slots=2, s_max=32,
        temperature=0.0, seed=0, loop_decode=False, prepare_weights=False,
        compress_tp=False, pace_us=0.0, queue_limit=8, host="127.0.0.1",
        port=0)
    door, _ = build_frontdoor(args, cfg, params, None)
    placed = [{d for leaf in jax.tree.leaves(w.batcher.params)
               for d in leaf.devices()} for w in door.router.workers]
    door.router.stop()
    assert all(len(d) == 1 for d in placed)
    assert len(set().union(*placed)) == 4
