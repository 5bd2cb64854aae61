"""Default-engine factory for the serving surface (ISSUE 19).

PAGED is the default serving engine for the front-end and the bench
ladder (its decode step is `paged_append_attend` once a layer, see
docs/serving.md). The slot-contiguous `DecodeEngine` stays available
behind ``PT_SERVE_ENGINE=contiguous`` (or ``engine="contiguous"``): it
is the sampling-policy surface (temperature/top-k live there), for
models whose layers keep keys and values. Which engine is faster on
the chip: not measured since PR 6.

``make_engine(model)`` is the one construction path the serving
front-end, the smoke tools and the bench ladder share — flipping the
fleet between engines is one env var, not a code edit.
"""

import math
import os
from typing import Optional

from paddle_tpu.inference.decode_engine import DecodeEngine
from paddle_tpu.inference.paged_engine import PagedDecodeEngine
from paddle_tpu.models import layer_kinds

__all__ = ["make_engine", "default_engine_kind"]


def default_engine_kind() -> str:
    """The serving default: ``PT_SERVE_ENGINE`` ('paged' unless
    overridden; 'contiguous' keeps the slot-contiguous engine)."""
    kind = os.environ.get("PT_SERVE_ENGINE", "paged")
    if kind not in ("paged", "contiguous"):
        raise ValueError(
            f"PT_SERVE_ENGINE must be 'paged' or 'contiguous', "
            f"got {kind!r}")
    return kind


def make_engine(model, engine: Optional[str] = None, *,
                max_slots: int = 8, max_len: Optional[int] = None,
                n_pages: Optional[int] = None, page_size: int = 128,
                steps_per_call: int = 1, **kw):
    """Build the serving engine for ``model``: ``engine`` (explicit)
    beats ``PT_SERVE_ENGINE`` beats the paged default.

    Paged sizing default, from the layers that keep pages
    (`models/layer_kinds.py`): enough pages for every slot to hold a
    full-length sequence (``max_slots * ceil(max_len / page_size)``:
    pages of keys and values, or of latent rows), and none for a model
    whose layers keep a state per sequence instead (the engine then
    holds a state pool of ``max_slots`` slots); a kind that prefills in
    chunks takes prompts of any length —
    the no-surprises envelope; real deployments size the pool to the
    LIVE-token budget instead (that over-commit is the engine's whole
    point) and pass ``n_pages`` explicitly. The decode step's cost does
    not depend on the pool's size (the pools are updated in place; on
    one v5e GPT-3 XL steps in 26.8 ms at 64 pages and at this default,
    256 pages at 16 slots, whose pool is 6.4 GB: PERF.md section 4),
    so the envelope costs memory only. Remaining kwargs pass
    through to the chosen engine's constructor (``speculative_k`` works
    on both)."""
    kind = engine if engine is not None else default_engine_kind()
    if engine is not None and engine not in ("paged", "contiguous"):
        raise ValueError(
            f"engine must be 'paged' or 'contiguous', got {engine!r}")
    cap = max_len or model.cfg.max_seq_len
    if kind == "paged":
        if n_pages is None:
            n_pages = (max_slots * math.ceil(cap / page_size)
                       if layer_kinds.kind_of(model.cfg).pages else 0)
        return PagedDecodeEngine(
            model, n_pages=n_pages, max_slots=max_slots,
            page_size=page_size, steps_per_call=steps_per_call, **kw)
    return DecodeEngine(model, max_slots=max_slots, max_len=cap,
                        steps_per_call=steps_per_call, **kw)
