"""Kernel block-size autotuning with a persistent per-shape cache.

Reference analog: paddle/phi/kernels/autotune/auto_tune_base.h (TuneBase —
measure every candidate kernel config on the real shapes, pick the
fastest) + autotune/cache.cc (AutoTuneCache — per-(kernel, shape-key)
result cache so tuning happens once). The TPU twist: Pallas block sizes
are trace-time constants, so tuning must happen EAGERLY (outside jit) —
``tune(...)`` measures candidates on device, and kernels consult the
cache at trace time (a pure Python dict read) when no explicit block
size is passed.

The cache persists to ``.pt_cache/autotune.json`` inside the checkout
(git-ignored; override: ``PT_AUTOTUNE_CACHE``): the second process run
hits the cache instead of re-measuring, matching the reference's
serialized cache behavior. The file is a RUN-TIME artifact — it is not
in what git commits, so a fresh checkout runs every kernel at its code
default until something calls ``tune``; a run that must not depend on
whatever an earlier sweep left on this disk (``chip_smoke.py``) starts
from ``get_cache().clear()``.
"""

import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["AutotuneCache", "get_cache", "tune"]


def _default_path() -> str:
    # beside the XLA compile cache, in the checkout's one cache dir
    from paddle_tpu.compile_cache import CHECKOUT_CACHE_DIR
    return os.environ.get(
        "PT_AUTOTUNE_CACHE",
        os.path.join(os.path.dirname(CHECKOUT_CACHE_DIR),
                     "autotune.json"))


class AutotuneCache:
    """(kernel, shape-key) → best config (≙ cache.cc AutoTuneCache)."""

    def __init__(self, path: Optional[str] = None):
        self.path = path if path is not None else _default_path()
        self._table: Dict[str, list] = {}
        self._loaded = False

    def _load(self):
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self.path) as f:
                self._table = json.load(f)
        except (OSError, ValueError):
            self._table = {}

    @staticmethod
    def key(kernel: str, **parts) -> str:
        return kernel + "|" + "|".join(
            f"{k}={parts[k]}" for k in sorted(parts))

    def get(self, key: str):
        self._load()
        hit = self._table.get(key)
        return tuple(hit) if isinstance(hit, list) else hit

    def put(self, key: str, config, persist: bool = True):
        self._load()
        self._table[key] = list(config) if isinstance(config, tuple) \
            else config
        if persist:
            try:
                os.makedirs(os.path.dirname(self.path), exist_ok=True)
                tmp = self.path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(self._table, f, indent=0, sort_keys=True)
                os.replace(tmp, self.path)
            except OSError:
                pass  # cache is an optimization; never fail the caller

    def clear(self):
        self._table = {}
        self._loaded = True


_GLOBAL: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = AutotuneCache()
    return _GLOBAL


def tune(kernel: str, key: str, candidates: Sequence,
         build_and_run: Callable, warmup: int = 1, iters: int = 3,
         cache: Optional[AutotuneCache] = None,
         geom_check: Optional[Callable] = None):
    """Measure every candidate config and cache the argmin
    (≙ auto_tune_base.h TuneBase::PickBestKernel).

    ``build_and_run(config)`` must execute the kernel end-to-end on the
    real shapes and block until the result is ready. Configs that raise
    (e.g. a block shape Mosaic rejects for this dtype) are skipped.
    ``geom_check(config)`` (ISSUE 20) is consulted FIRST: a truthy
    return is a static refusal reason (e.g. ptgeom's PT006 VMEM budget)
    and the candidate is skipped without ever being built or timed —
    chip-time sweeps stop burning iterations on geometries that cannot
    fit. Returns (best_config, {config: seconds}); the winner lands in
    the cache keyed by ``key``.
    """
    cache = cache or get_cache()
    hit = cache.get(key)
    if hit is not None:
        return hit, {}
    timings: Dict = {}
    refused: Dict = {}
    last_exc = None
    for config in candidates:
        ckey = tuple(config) if isinstance(config, (list, tuple)) \
            else config
        if geom_check is not None:
            try:
                reason = geom_check(config)
            except Exception:  # a broken guard must not block tuning
                reason = None
            if reason:
                refused[ckey] = str(reason)
                continue
        try:
            build_and_run(config)  # compile + first run
            for _ in range(warmup):
                build_and_run(config)
            t0 = time.perf_counter()
            for _ in range(iters):
                build_and_run(config)
            timings[ckey] = (time.perf_counter() - t0) / iters
        except Exception as e:  # a config the backend rejects is skipped
            last_exc = e
            continue
    if not timings:
        detail = ""
        if refused:
            detail = "; geometry-refused: " + "; ".join(
                f"{k}: {v}" for k, v in refused.items())
        raise ValueError(f"autotune({kernel}): every candidate failed "
                         f"for key {key}{detail}") from last_exc
    best = min(timings, key=timings.get)
    cache.put(key, best)
    return best, timings
