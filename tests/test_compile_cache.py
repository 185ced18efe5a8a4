"""Where ``repro.utils.compile_cache.enable`` puts JAX's persistent cache.

Each case runs in a child process: the cache is process-wide state, and the
tests of this suite must not write to it.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")

_CHILD = textwrap.dedent("""
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    hits = []
    jax.monitoring.register_event_listener(
        lambda e, **_: hits.append(e)
        if e == "/jax/compilation_cache/cache_hits" else None)
    import repro.launch.solve  # importing an entry point enables nothing
    assert jax.config.jax_compilation_cache_dir == {before!r}, \\
        jax.config.jax_compilation_cache_dir
    from repro.utils import compile_cache
    path = compile_cache.enable()
    if {compile!r}:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
    print("DIR", path, jax.config.jax_compilation_cache_dir)
    print("HITS", len(hits))
""")


def _run(env_dir, *, compile_=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    script = _CHILD.format(src=SRC, before=env_dir, compile=compile_)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in out.stdout.splitlines()
                 if line.startswith(("DIR ", "HITS ")))
    return lines["DIR"].split(), int(lines["HITS"])


def test_env_dir_is_used_and_a_second_run_hits(tmp_path):
    cache = str(tmp_path / "cache")
    (path, configured), hits = _run(cache)
    assert path == configured == cache
    assert hits == 0 and os.listdir(cache)
    entries = sorted(os.listdir(cache))
    (_, _), hits = _run(cache)
    assert hits >= 1
    assert sorted(os.listdir(cache)) == entries   # nothing new was written


@pytest.mark.parametrize("env_dir", [None, ""], ids=["unset", "empty"])
def test_default_is_a_fixed_path_in_the_checkout(env_dir):
    (path, configured), _ = _run(env_dir, compile_=False)
    assert path == configured == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
