"""One rule places the persistent compile cache for every entry point
(paddle_tpu/utils/compile_cache.py): JAX_COMPILATION_CACHE_DIR if set —
then nothing in the repo sets a directory — else one fixed directory
inside the checkout."""
import os

import jax
import pytest

from paddle_tpu.utils import compile_cache as cc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved[1])


def test_unset_uses_the_fixed_checkout_directory(monkeypatch,
                                                 restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert cc.configure_compile_cache() == cc.CHECKOUT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == cc.CHECKOUT_CACHE_DIR
    assert cc.CHECKOUT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")


def test_environment_placement_is_left_alone(monkeypatch, tmp_path,
                                             restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert cc.configure_compile_cache(0.5) == str(tmp_path)
    # nothing was set in code: jax keeps what it had (it reads the
    # variable itself at start-up)
    assert jax.config.jax_compilation_cache_dir == "sentinel"
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5


def test_no_other_file_sets_a_cache_directory():
    """The helper is the only place in the repo that names the config
    key; a second setter would move the cache (the path is part of its
    key) or override the environment."""
    named = set()
    for top, dirs, files in os.walk(ROOT):
        # skip caches, git-ignored scratch copies (_parent/ ...) and .git
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))]
        for f in files:
            if f.endswith((".py", ".sh")):
                path = os.path.join(top, f)
                with open(path, errors="replace") as fh:
                    if "jax_compilation_cache_dir" in fh.read():
                        named.add(os.path.relpath(path, ROOT))
    assert named == {"paddle_tpu/utils/compile_cache.py",
                     "tests/test_compile_cache.py"}, named
