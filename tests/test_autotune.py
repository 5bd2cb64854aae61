"""Kernel block-size autotune (VERDICT r3 item 8).

Reference analog: phi/kernels/autotune tests (auto_tune_test.cu pattern —
pick-best over measured candidates + cache hit on the second query)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import autotune as at
from paddle_tpu.ops.pallas.flash_attention import (_DEFAULT_BLOCKS,
                                                   _tune_key,
                                                   flash_attention,
                                                   tune_flash_attention)


@pytest.fixture
def cache(tmp_path, monkeypatch):
    c = at.AutotuneCache(path=str(tmp_path / "autotune.json"))
    monkeypatch.setattr(at, "_GLOBAL", c)
    return c


def test_tune_picks_argmin_and_caches(cache):
    calls = []

    def build_and_run(cfg):
        calls.append(cfg)
        import time
        time.sleep({"slow": 0.01, "fast": 0.0, "bad": 0.0}[cfg])
        if cfg == "bad":
            raise ValueError("unsupported config")

    best, timings = at.tune("k", "key1", ["slow", "bad", "fast"],
                            build_and_run, warmup=0, iters=2,
                            cache=cache)
    assert best == "fast"
    assert "bad" not in timings
    n = len(calls)

    # second query: cache hit, no measurement
    best2, timings2 = at.tune("k", "key1", ["slow", "fast"],
                              build_and_run, cache=cache)
    assert best2 == best
    assert timings2 == {} and len(calls) == n


def test_cache_persists_across_instances(tmp_path):
    c1 = at.AutotuneCache(path=str(tmp_path / "t.json"))
    c1.put("k|a=1", (128, 256))
    c2 = at.AutotuneCache(path=str(tmp_path / "t.json"))
    assert c2.get("k|a=1") == (128, 256)
    assert c2.get("k|a=2") is None


def test_every_candidate_failing_raises(cache):
    def boom(cfg):
        raise RuntimeError("no")

    with pytest.raises(ValueError, match="every candidate failed"):
        at.tune("k", "key2", [1, 2], boom, cache=cache)


def test_flash_attention_reads_tuned_blocks(cache, monkeypatch):
    """A cache entry for the exact shape key changes the blocks the kernel
    traces with; absent an entry, the measured defaults apply."""
    import sys
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]

    b, s, h, d = 2, 256, 2, 64
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)

    seen = {}
    real = fa._flash

    def spy(*args, **kw):
        # (..., block_q, block_k, ...) positional: capture via the two
        # ints right after the scale argument
        seen["blocks"] = (args[8], args[9])
        return real(*args, **kw)

    monkeypatch.setattr(fa, "_flash", spy)
    flash_attention(q, q, q, causal=True)
    # a 256-length seq clamps the default blocks to (256, 256)
    assert seen["blocks"] == (min(_DEFAULT_BLOCKS[0], 256),
                              min(_DEFAULT_BLOCKS[1], 256))

    key = _tune_key(b, s, s, h, h, d, q.dtype, True, False, False, False)
    cache.put(key, (128, 128))
    flash_attention(q, q, q, causal=True)
    assert seen["blocks"] == (128, 128)

    # explicit blocks always win over the cache
    flash_attention(q, q, q, causal=True, block_q=256, block_k=128)
    assert seen["blocks"] == (256, 128)


def test_tune_flash_attention_end_to_end(cache):
    """Eager sweep on CPU (interpret mode): winner persisted under the key
    flash_attention's trace-time lookup uses."""
    b, s, h, d = 1, 128, 1, 8
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
    best, timings = tune_flash_attention(
        q, q, q, causal=True, candidates=[(128, 128)], include_bwd=False,
        iters=1)
    assert best == (128, 128) and timings
    key = _tune_key(b, s, s, h, h, d, q.dtype, True, False, False, False)
    assert cache.get(key) == (128, 128)
    # numerics with the tuned blocks still match the XLA reference
    out = flash_attention(q, q, q, causal=True)
    ref = jax.nn.softmax(
        jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                  (q[:, :, 0] @ q[:, :, 0].transpose(0, 2, 1))
                  / np.sqrt(d), -1e30)) @ q[:, :, 0]
    np.testing.assert_allclose(np.asarray(out[:, :, 0]), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
