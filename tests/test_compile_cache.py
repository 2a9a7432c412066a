"""The persistent compilation cache is placed from outside the code: where
JAX_COMPILATION_CACHE_DIR is set it is used as is, otherwise the cache is
the checkout's fixed .jax_cache/. Each case runs in a child process, so
this test process never turns the cache on."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[1]

CHILD = textwrap.dedent("""
    import json, os
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
    print(json.dumps(dict(before=before, where=where,
                          config=jax.config.jax_compilation_cache_dir)))
""")


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, timeout=300, env=env, cwd=str(ROOT))
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_lands_in_env_dir(tmp_path):
    out = _run(tmp_path)
    assert out["where"] == out["config"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was cached in the env dir"


def test_cache_defaults_to_checkout_dir_without_env():
    from repro.launch.compile_cache import CHECKOUT_CACHE
    assert CHECKOUT_CACHE == ROOT / ".jax_cache"
    # the path is fixed: no temp name, process id or time in it
    out = _run(None)
    assert out["before"] is None
    assert out["where"] == out["config"] == str(ROOT / ".jax_cache")
    assert any((ROOT / ".jax_cache").iterdir()), \
        "nothing was cached in the checkout's .jax_cache/"
