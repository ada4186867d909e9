"""Persistent XLA compilation cache (core/cache.py): where it is placed,
and the actual hit/miss behavior across process restarts.

The cache configuration is process-global, so every case runs
``enable_compile_cache`` in child processes and reads back what they
report; the children are shared between the cases through module
fixtures (a JAX import each is the cost)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from routest_tpu.core.cache import COMPILE_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp
    from routest_tpu.core import cache
    if len(sys.argv) > 2:
        cache.COMPILE_CACHE_DIR = sys.argv[2]
    report = {"returned": cache.enable_compile_cache(), "pid": os.getpid(),
              "cwd": os.getcwd()}
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.jit(lambda x: jnp.tanh(x @ x).sum())(
            jnp.ones((256, 256))).block_until_ready()
    report.update(
        configured=jax.config.jax_compilation_cache_dir,
        min_secs=jax.config.jax_persistent_cache_min_compile_time_secs,
        min_bytes=jax.config.jax_persistent_cache_min_entry_size_bytes)
    print(json.dumps(report))
""")


def _child(cwd, env_dir=None, default_dir=None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    argv = [sys.executable, "-c", _CHILD, REPO]
    if default_dir:
        argv.append(default_dir)
    proc = subprocess.run(argv, env=env, cwd=str(cwd), capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.splitlines()[-1])


def _program_entries(cache: str) -> dict:
    # jax maintains "*-atime" sidecar files per cache entry and REWRITES
    # them on every cache read (LRU eviction bookkeeping) — a rewritten
    # atime is evidence of a hit, not of a recompile, so the reuse
    # assertion must ignore them.
    return {e: os.path.getmtime(os.path.join(cache, e))
            for e in os.listdir(cache) if not e.endswith("-atime")}


@pytest.fixture(scope="module")
def placed(tmp_path_factory):
    """Two processes with JAX_COMPILATION_CACHE_DIR set, each compiling
    the same program: (directory, first report, entries after the first,
    second report, entries after the second)."""
    cache = str(tmp_path_factory.mktemp("placed") / "xla")
    first = _child(REPO, env_dir=cache)
    after_first = _program_entries(cache)
    second = _child(REPO, env_dir=cache)
    return cache, first, after_first, second, _program_entries(cache)


@pytest.fixture(scope="module")
def unplaced(tmp_path_factory):
    """Two processes with the variable unset, started in different
    working directories."""
    return (_child(REPO), _child(tmp_path_factory.mktemp("elsewhere")))


def test_env_directory_is_honoured_and_not_overwritten(placed):
    cache, first, _, second, _ = placed
    for report in (first, second):
        assert report["returned"] == cache
        # JAX read the variable itself; the code set no directory of
        # its own over it.
        assert report["configured"] == cache
    assert not os.path.exists(os.path.join(cache, ".jax_cache"))


def test_thresholds_cache_everything_in_both_placements(placed, unplaced):
    for report in (placed[1], unplaced[0]):
        assert report["min_secs"] == 0
        assert report["min_bytes"] == -1


def test_default_is_one_fixed_directory_inside_the_checkout(unplaced):
    assert COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert unplaced[0]["returned"] == unplaced[0]["configured"] \
        == COMPILE_CACHE_DIR
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_same_default_from_two_processes(unplaced):
    here, elsewhere = unplaced
    assert here["pid"] != elsewhere["pid"]
    assert here["cwd"] != elsewhere["cwd"]
    # The directory is part of every entry's key: a path built from the
    # working directory, the process id or the time could never hit.
    assert here["returned"] == elsewhere["returned"] == COMPILE_CACHE_DIR


def test_cache_persists_across_processes(placed):
    _, _, after_first, _, after_second = placed
    assert after_first, "first run wrote no cache entries"
    # The second process reused the entries rather than recompiling:
    # nothing new for this program was written, nothing rewritten.
    assert after_second == after_first


def test_unusable_default_runs_uncached(tmp_path):
    planted = tmp_path / "planted"
    planted.write_text("not a directory")
    report = _child(REPO, default_dir=str(planted / "sub"))
    assert report["returned"] is None
    assert report["configured"] is None
