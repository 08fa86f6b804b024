"""chip_smoke.py and the compile cache, as far as a CPU can check them.

The chip run itself is made through the chip tool (README "Running it").
"""
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from skypilot_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parent_does_not_import_jax():
    """A parent that has touched JAX holds the chip its children need."""
    out = subprocess.run(
        [sys.executable, '-c',
         'import sys, chip_smoke; print("jax" in sys.modules)'],
        cwd=REPO, capture_output=True, text=True, check=True)
    assert out.stdout.strip() == 'False'


@pytest.fixture
def cache_config():
    """enable() changes global JAX config: put it back."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update('jax_compilation_cache_dir', before)


def test_compile_cache_leaves_env_dir_alone(cache_config, monkeypatch,
                                            tmp_path):
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_path_is_fixed(cache_config, monkeypatch, tmp_path):
    """The directory is part of the cache key: the same one from any
    working directory, inside the checkout."""
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    paths = []
    for cwd in (tmp_path, REPO):
        monkeypatch.chdir(cwd)
        paths.append(compile_cache.enable())
        assert jax.config.jax_compilation_cache_dir == paths[-1]
    assert paths[0] == paths[1] == os.path.join(REPO, '.jax_cache')


def test_bypassed_compiles_leave_no_entry(cache_config, tmp_path):
    """Pinned-layout executables must not come back from the persistent
    cache (chip run, PR 22): inside `bypassed()` nothing is written or
    read, and the cache works again afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update('jax_compilation_cache_dir', str(tmp_path))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    compilation_cache.reset_cache()

    def entries():
        return [f for f in os.listdir(tmp_path) if f.endswith('-cache')]

    try:
        with compile_cache.bypassed():
            jax.jit(lambda x: x * 3 + 1).lower(1.0).compile()
        assert entries() == []
        jax.jit(lambda x: x * 5 + 2).lower(1.0).compile()
        assert len(entries()) == 1
    finally:
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          min_s)
        jax.config.update('jax_compilation_cache_dir', None)
        compilation_cache.reset_cache()


def test_no_chip_means_not_ok():
    """No accelerator here: the run fails, whatever JAX_PLATFORMS says."""
    out = subprocess.run(
        [sys.executable, 'chip_smoke.py'], cwd=REPO, capture_output=True,
        text=True, check=False, env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode != 0
    assert json.loads(out.stdout.strip().splitlines()[-1])['ok'] is False


@pytest.mark.slow
@pytest.mark.parametrize('chips', [1, 4])
def test_rehearsal_runs_every_phase(chips):
    """The whole control flow at `tiny` on the CPU (each child imports
    JAX: half a minute).  It can never be taken for a chip run."""
    out = subprocess.run(
        [sys.executable, 'chip_smoke.py', '--rehearse', '--chips',
         str(chips)], cwd=REPO, capture_output=True, text=True, check=False)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert [l['phase'] for l in lines[:-1]] == list(chip_smoke.PHASES[chips])
    assert lines[-1] == {
        'ok': True, 'rehearsal': True,
        'device': {'platform': 'cpu', 'kind': 'cpu', 'count': chips}}
