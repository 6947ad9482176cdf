"""Where the persistent compilation cache lives (utils/jaxcache.py)."""

from pathlib import Path

from selkies_tpu.utils import jaxcache

ROOT = Path(__file__).resolve().parents[1]


def test_cache_dir_honours_env():
    env = {"JAX_COMPILATION_CACHE_DIR": "/srv/jax-cache"}
    assert jaxcache.cache_dir(env) == "/srv/jax-cache"


def test_cache_dir_defaults_to_ignored_checkout_path():
    assert jaxcache.cache_dir({}) == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
