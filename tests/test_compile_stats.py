"""Regression tests for the compile-once accounting itself: per-name
watcher snapshots (late-registered twins count) and strict dynamic
launch registration."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_stats


@pytest.fixture
def scratch_names():
    """Pop any launch names a test registers, keeping _DYNAMIC clean
    for the rest of the suite."""
    names = []
    yield names
    for name in names:
        compile_stats._DYNAMIC.pop(name, None)


def test_late_registered_twin_counts_as_miss(scratch_names):
    """A sharded twin minted AFTER a watcher was constructed must have
    its first compile attributed to that watcher — under the old
    single-total snapshot the twin was invisible (absent from the base
    resolution), so serving-time compiles went uncounted."""
    watcher = compile_stats.CompileWatcher()
    twin = jax.jit(lambda x: x * 2.0)
    scratch_names.append("cs_test_late_twin")
    compile_stats.register_launch("cs_test_late_twin", twin)
    assert watcher.misses() == 0          # registered, not yet compiled
    twin(jnp.ones((3,)))
    assert watcher.misses() == 1
    twin(jnp.ones((3,)))                  # cache hit: no new miss
    assert watcher.misses() == 1
    twin(jnp.ones((5,)))                  # new shape: one more
    assert watcher.misses() == 2
    watcher.reset()
    assert watcher.misses() == 0


def test_reregistering_same_fn_is_idempotent(scratch_names):
    twin = jax.jit(lambda x: x + 1.0)
    scratch_names.append("cs_test_idempotent")
    compile_stats.register_launch("cs_test_idempotent", twin)
    compile_stats.register_launch("cs_test_idempotent", twin)
    assert compile_stats.tracked_launches()["cs_test_idempotent"] \
        is twin


def test_reregistering_different_fn_raises(scratch_names):
    """Replacing a name's fn would drop the old twin's cache entries
    from the accounting and mask real misses."""
    scratch_names.append("cs_test_clash")
    compile_stats.register_launch("cs_test_clash",
                                  jax.jit(lambda x: x + 1.0))
    with pytest.raises(ValueError, match="different"):
        compile_stats.register_launch("cs_test_clash",
                                      jax.jit(lambda x: x + 2.0))


def test_registering_a_static_name_raises():
    """The merged tracked dict gives static names precedence; a dynamic
    registration under one would be silently ignored."""
    with pytest.raises(ValueError, match="static vocabulary"):
        compile_stats.register_launch("fit", jax.jit(lambda x: x))
    assert "fit" not in compile_stats._DYNAMIC


def test_static_name_guard_covers_whole_vocabulary():
    assert compile_stats._STATIC_NAMES == \
        set(compile_stats.tracked_launches()) - \
        set(compile_stats._DYNAMIC)


def test_watcher_immune_to_other_launches_base(scratch_names):
    """Per-name bases: one launch's pre-existing cache entries can
    never offset another launch's misses."""
    warm = jax.jit(lambda x: x - 1.0)
    scratch_names.append("cs_test_warm")
    compile_stats.register_launch("cs_test_warm", warm)
    warm(jnp.ones((2,)))
    watcher = compile_stats.CompileWatcher()
    cold = jax.jit(lambda x: x * 3.0)
    scratch_names.append("cs_test_cold")
    compile_stats.register_launch("cs_test_cold", cold)
    cold(jnp.ones((2,)))
    assert watcher.misses() == 1


def test_launch_without_jit_cache_raises(scratch_names):
    """A tracked launch with no jit cache cannot be counted: the
    accounting refuses it instead of reading zero compiles forever."""
    scratch_names.append("cs_test_plain_fn")
    compile_stats.register_launch("cs_test_plain_fn", lambda x: x)
    with pytest.raises(TypeError, match="no jit cache"):
        compile_stats.CompileWatcher()


def test_compile_cache_dir_env_wins_else_checkout(monkeypatch, tmp_path):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_stats.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == \
            saved["jax_compilation_cache_dir"]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert compile_stats.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
