"""Persistent-compilation-cache hardening (ISSUE 4 satellite): flaky
cache entries (BENCH r05's RESOURCE_EXHAUSTED read warnings) must be
COUNTED into serve/compile_cache_errors and printed once, never spam or
abort a serving process; enabling a broken cache falls back to cold
compiles instead of raising."""

import warnings

import pytest

from paddle_tpu import compile_cache, stats


@pytest.fixture
def fresh_guard(monkeypatch):
    """Reinstall the guard over a recording stub so the test sees what
    would reach the user, regardless of prior installs in-process."""
    shown = []
    monkeypatch.setattr(
        warnings, "showwarning",
        lambda message, *a, **k: shown.append(str(message)))
    monkeypatch.setattr(compile_cache, "_hook", None)
    monkeypatch.setattr(compile_cache, "_printed", False)
    compile_cache.guard()
    return shown


def test_cache_warnings_counted_and_printed_once(fresh_guard):
    shown = fresh_guard
    stats.reset("serve/compile_cache_errors")
    msg = ("Error reading persistent compilation cache entry for "
           "'jit_convert_element_type': JaxRuntimeError: "
           "RESOURCE_EXHAUSTED: TPU backend error (ResourceExhausted).")
    for _ in range(3):
        warnings.warn(msg)
    assert stats.get("serve/compile_cache_errors") == 3
    assert sum("persistent compilation cache" in s for s in shown) == 1

    # unrelated warnings pass through untouched and uncounted
    warnings.warn("something else entirely", stacklevel=1)
    assert any("something else" in s for s in shown)
    assert stats.get("serve/compile_cache_errors") == 3


def test_failure_records_class_and_disabled_gauge(fresh_guard):
    """ISSUE 15 satellite: a cache failure is triageable from /statsz —
    per-exception-class counter, the prof/compile_cache_disabled gauge
    latched, and status() carries the class for bench provenance."""
    stats.reset("serve/compile_cache_errors")
    stats.reset("prof/compile_cache_disabled")
    warnings.warn("Error reading persistent compilation cache entry "
                  "for 'jit_x': JaxRuntimeError: RESOURCE_EXHAUSTED: "
                  "TPU backend error (ResourceExhausted).")
    assert stats.get("serve/compile_cache_errors") == 1
    assert stats.get(
        "serve/compile_cache_errors/JaxRuntimeError") == 1
    assert stats.get("prof/compile_cache_disabled") == 1.0
    st = compile_cache.status()
    assert st["disabled"] and st["errors"] == 1
    assert st["last_error_class"] == "JaxRuntimeError"
    # a classless message still counts, under "unknown"
    warnings.warn("Error writing persistent compilation cache entry "
                  "for 'jit_y': disk full")
    assert stats.get("serve/compile_cache_errors/unknown") == 1
    assert compile_cache.status()["errors"] == 2


def test_enable_honours_env_cache_dir(fresh_guard, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the cache is placed from outside
    — enable() installs the guard and sets NO directory in code (jax
    reads the variable itself)."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, val: updates.append(key))
    compile_cache.enable()
    assert "jax_compilation_cache_dir" not in updates
    assert warnings.showwarning is compile_cache._hook


def test_guard_is_idempotent(fresh_guard):
    hook = warnings.showwarning
    compile_cache.guard()
    compile_cache.guard()
    assert warnings.showwarning is hook


def test_guard_reinstalls_after_displacement(fresh_guard):
    """A warnings.catch_warnings() exit (or any library swapping
    showwarning) displaces the hook; the next guard() call — every
    engine construction — must re-install it."""
    displaced = []
    warnings.showwarning = lambda message, *a, **k: \
        displaced.append(str(message))
    compile_cache.guard()
    assert warnings.showwarning is compile_cache._hook
    from paddle_tpu import stats
    stats.reset("serve/compile_cache_errors")
    warnings.warn("Error reading persistent compilation cache entry")
    assert stats.get("serve/compile_cache_errors") == 1
    assert displaced   # chained through to the displaced hook


def test_guard_env_opt_out(fresh_guard, monkeypatch):
    monkeypatch.setenv("PT_COMPILE_CACHE_GUARD", "0")
    hook = warnings.showwarning
    warnings.showwarning = hook2 = lambda *a, **k: None
    compile_cache.guard()
    assert warnings.showwarning is hook2   # untouched
    warnings.showwarning = hook


def test_enable_resolves_fixed_checkout_dir(fresh_guard, monkeypatch):
    """Variable unset: every call from this checkout resolves to the
    SAME git-ignored directory inside it (the path is part of jax's
    cache key — a tempfile/pid/time-derived directory never hits)."""
    import os
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        first, second = compile_cache.enable(), compile_cache.enable()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == compile_cache.CHECKOUT_CACHE_DIR
    assert first == os.path.join(repo, ".pt_cache", "xla")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".pt_cache/" in f.read().split()


def test_engines_install_guard(monkeypatch):
    import jax.numpy as jnp
    from paddle_tpu.inference.decode_engine import DecodeEngine
    from paddle_tpu.models import gpt

    monkeypatch.setattr(compile_cache, "_hook", None)
    cfg = gpt.GPTConfig(vocab_size=96, max_seq_len=64, d_model=32,
                        n_layers=2, n_heads=4, dtype=jnp.float32)
    DecodeEngine(gpt.GPT(cfg, seed=0), max_slots=1, max_len=64)
    assert (compile_cache._hook is not None
            and warnings.showwarning is compile_cache._hook)
