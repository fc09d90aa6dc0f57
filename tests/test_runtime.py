"""The process-wide interpret switch and compile cache (core/runtime.py).

Pure plumbing tests — no compiled-mode execution (CPU CI has no device to
compile Pallas for): the default follows the platform, explicit flags win,
flipping the switch fires the registered cache-reset hooks exactly once per
real change, the registered backends defer to the process default
(interpret=None) rather than pinning their own, and the compile cache lands
where `JAX_COMPILATION_CACHE_DIR` says or in the checkout's `.jax_cache/`.
"""
import pathlib

import jax
import pytest

from repro.core import backends as B
from repro.core import runtime


@pytest.fixture(autouse=True)
def _restore_interpret():
    before = runtime.interpret_default()
    yield
    runtime.set_interpret(before)


def test_resolve_explicit_wins_none_follows_default():
    assert runtime.resolve_interpret(None) == runtime.interpret_default()
    assert runtime.resolve_interpret(True) is True
    assert runtime.resolve_interpret(False) is False
    runtime.set_interpret(False)
    assert runtime.resolve_interpret(None) is False
    assert runtime.resolve_interpret(True) is True


def test_set_interpret_fires_hooks_only_on_change():
    calls = []
    hook = lambda: calls.append(1)
    runtime.register_reset_hook(hook)
    try:
        start = runtime.interpret_default()
        runtime.set_interpret(start)          # no-op: unchanged
        assert calls == []
        runtime.set_interpret(not start)
        assert calls == [1]
        runtime.set_interpret(not start)      # no-op again
        assert calls == [1]
    finally:
        runtime._RESET_HOOKS.remove(hook)


def test_registered_backends_follow_process_default():
    """No registered backend pins its own interpret mode — one switch moves
    the whole stack (the satellite contract this PR introduced)."""
    for name in B.list_backends():
        be = B.get_backend(name)
        flag = getattr(be, "interpret", None)
        assert flag is None, (
            f"backend {name!r} pins interpret={flag!r}; it must default to "
            f"None so backends.set_interpret governs it")
    assert B.set_interpret is runtime.set_interpret
    assert B.interpret_default is runtime.interpret_default


def test_default_follows_platform(monkeypatch):
    """Interpret exactly on a CPU backend; compiled kernels elsewhere."""
    monkeypatch.setattr(runtime, "_INTERPRET", None)
    assert runtime.interpret_default() == (jax.default_backend() == "cpu")


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert runtime.init_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets no other directory
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = pathlib.Path(__file__).resolve().parents[1]
        path = runtime.init_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
