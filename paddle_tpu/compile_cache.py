"""Persistent-compilation-cache hardening (ISSUE 4 satellite).

BENCH r05 logged ``RESOURCE_EXHAUSTED: TPU backend error`` UserWarnings
from persistent-cache reads mid-bench: jax treats a failed cache
read/write as a warning and recompiles, which is the right fallback —
but a serving process then prints one warning line per flaky entry
(spam), and an operator has no counter to tell a degraded cache from a
healthy one. This module:

- ``guard()`` — routes jax's per-entry compilation-cache failure
  warnings into the stats registry (``serve/compile_cache_errors``,
  plus a per-exception-class counter and the
  ``prof/compile_cache_disabled`` gauge), printing only the FIRST
  occurrence; every other warning passes through untouched. Installed
  idempotently by both decode engines at construction.
- ``enable()`` — turns jax's persistent compilation cache on. WHERE it
  lives is decided outside the code: with ``JAX_COMPILATION_CACHE_DIR``
  set, jax's own setting stands and no directory is set here;
  otherwise it is ONE fixed directory inside the checkout
  (``.pt_cache/xla``, git-ignored) — the path is part of the cache key,
  so a directory that moves (tempfile, pid, time) never hits. A broken
  directory surfaces as jax's per-entry warnings, which ``guard()``
  counts: cold compiles are a slowdown, not an outage. Both decode
  engines and ``chip_smoke.py`` call it.
- ``status()`` — {"disabled", "errors", "last_error_class"} for bench
  provenance: the r05 RESOURCE_EXHAUSTED that silently killed the
  bert/resnet/ppyoloe rows is now a stamped field on every BENCH
  snapshot and a /statsz gauge, not a line lost in stderr.

docs/serving.md documents the operator contract.
"""

import os
import re
import threading
import warnings

__all__ = ["guard", "enable", "status", "CHECKOUT_CACHE_DIR"]

# matches jax's "Error reading persistent compilation cache entry ..."
# and "Error writing persistent compilation cache entry ..." warnings
_MATCH = re.compile(r"persistent compilation cache", re.IGNORECASE)
# the exception class jax embeds in the warning text ("...: JaxRuntimeError:
# RESOURCE_EXHAUSTED: ..."); the class name is the triage key (a flaky
# read vs a full disk vs a permission wall are different runbooks)
_EXC_CLASS = re.compile(r"\b([A-Za-z_][A-Za-z0-9_]*(?:Error|Exception))\b")
#: the one in-checkout cache directory (used when the environment does
#: not place the cache itself)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".pt_cache", "xla")
_lock = threading.Lock()
_hook = None
_printed = False
_last_exc_class = None


def _record_failure(exc_class: str):
    """Count one cache failure: total + per-class counters, and latch
    the ``prof/compile_cache_disabled`` gauge (the cache is degraded —
    compiles fall back to cold — until an operator intervenes)."""
    global _last_exc_class
    from paddle_tpu import stats
    _last_exc_class = exc_class
    stats.add("serve/compile_cache_errors")
    stats.add(f"serve/compile_cache_errors/{exc_class}")
    stats.set_value("prof/compile_cache_disabled", 1.0)


def status() -> dict:
    """Provenance view of the cache's health this process: whether any
    failure latched the disabled gauge, the total error count, and the
    most recent exception class (None when healthy)."""
    from paddle_tpu import stats
    return {
        "disabled": bool(stats.get("prof/compile_cache_disabled", 0)),
        "errors": int(stats.get("serve/compile_cache_errors", 0)),
        "last_error_class": _last_exc_class,
    }


def guard() -> None:
    """Idempotently intercept compilation-cache failure warnings: every
    occurrence increments ``serve/compile_cache_errors``; only the
    first is shown. Never raises.

    The hook and its "always" filter mutate process-global ``warnings``
    state (an intervening ``warnings.catch_warnings()`` context restores
    the previous hook on exit, so guard() re-installs whenever it finds
    itself displaced — every engine construction calls it). Set
    ``PT_COMPILE_CACHE_GUARD=0`` to opt out entirely (e.g. a process
    run under ``-W ignore`` that wants no cache-failure line at all)."""
    global _hook
    if os.environ.get("PT_COMPILE_CACHE_GUARD", "1") == "0":
        return
    with _lock:
        if _hook is not None and warnings.showwarning is _hook:
            return   # still installed
        prev = warnings.showwarning

        def _showwarning(message, category, filename, lineno,
                         file=None, line=None):
            global _printed
            text = str(message)
            if _MATCH.search(text):
                m = _EXC_CLASS.search(text)
                _record_failure(m.group(1) if m else "unknown")
                if _printed:
                    return
                _printed = True
            prev(message, category, filename, lineno, file, line)

        warnings.showwarning = _showwarning
        _hook = _showwarning
        # the default "once per call site" filter would hide repeats
        # from the hook above — the hook dedupes the printing itself
        warnings.filterwarnings(
            "always", message=".*persistent compilation cache.*")


def enable() -> str:
    """Turn on jax's persistent compilation cache and return the
    directory it uses. ``JAX_COMPILATION_CACHE_DIR`` set: jax's own
    setting is left alone and no directory is set in code. Unset: the
    fixed in-checkout ``CHECKOUT_CACHE_DIR``."""
    guard()
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
