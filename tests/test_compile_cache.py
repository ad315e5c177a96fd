"""Where the entry points put JAX's persistent compilation cache, and
what keys it.

``$JAX_COMPILATION_CACHE_DIR`` wins and nothing sets another directory;
without it the cache is the fixed ``<checkout>/.jax_cache``.  Programs
that differ only in their op names get entries of their own.  Checked in
a child process, which starts with a fresh JAX config.
"""
import os
import subprocess
import sys
from pathlib import Path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.launch.cache import use_compile_cache
where = use_compile_cache()
print(where)
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(17.0)).block_until_ready()
"""


def _run(env):
    env = dict(env, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_env_dir_holds_the_entries(tmp_path):
    cache = tmp_path / "jaxcache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    where, config = _run(env)
    assert where == config == str(cache)
    assert any(cache.iterdir()), "no compiled entry in the cache dir"


def test_default_is_the_checkout_cache():
    from repro.launch.cache import CHECKOUT_CACHE
    assert CHECKOUT_CACHE == Path(ROOT) / ".jax_cache"
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    where, config = _run(env)
    assert where == config == str(CHECKOUT_CACHE)


_SCOPES = r"""
import os
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.launch.cache import use_compile_cache
where = use_compile_cache()


def scoped(name):
    def f(x):
        with jax.named_scope(name):
            return jnp.sin(x) * 3.0 + 1.0
    return f


for name in ("pin", "relayout", "pin"):
    jax.jit(scoped(name))(jnp.arange(17.0)).block_until_ready()
print(sum(e.startswith("jit_f-") for e in os.listdir(where)))
"""


def test_op_names_key_the_cache(tmp_path):
    """A cached executable carries the op names it was compiled with, and
    the profiler reports those: the same program under another scope must
    compile anew, the same scope again must hit."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SCOPES], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["2"]
