"""Paged continuous-batching decode engine: serving over a shared page
pool (the memory model half of vLLM-style serving; no reference analog —
the reference's fused_multi_transformer serves one contiguous CacheKV
per sequence).

Where `DecodeEngine` reserves max_len cache for every slot, this engine
holds ceil(len/page) pages per sequence from one pool and frees them at
retirement — HBM scales with the sum of LIVE tokens, so many more
sequences fit in flight at mixed lengths.

TPU design decisions:

- **Layer-folded pool**: the per-layer pools are one (L*P, Hkv, page, D)
  array; layer l's view of page p is id ``l*P + p``. The paged kernel
  receives the WHOLE pool and the per-layer table (``l*P + table``)
  selects its pages at DMA-schedule time — no per-layer slicing of the
  pool (a lax.dynamic_slice there would copy the full layer pool every
  step).
- **An admission is one program**: the jitted prefill (one-pass,
  suffix-only, or a state kind's last chunk) also installs the slot's
  decode state (``lengths``, ``last``, ``active``, ``remaining``,
  ``eos_ids``; the speculative history row) through `_install_slot`,
  and a retirement writes nothing, the program that sampled the last
  token having cleared ``active``. No eager program runs between two
  jitted dispatches of the steady state (docs/serving.md).
- **One decode step**: each layer of a `lax.scan` calls its kind's
  ``step`` (`models/layer_kinds.py`) with the pools as the carry. A
  softmax layer calls `paged_append_attend`, two launches: a small
  write kernel merges the current token's fresh KV row into its pool
  page in place (the layer-folded pools are that call's only pool
  operands, aliased to its outputs; the write target is derived from
  the block table + per-slot length, inactive slots write the scratch
  page), then the read-only attend runs over the pools the write
  returned, at ``lengths + 1``, walking the slot's live pages. Nothing
  copies the pools: they stay in place through the layer scan and the
  chunk scan (`tests/test_chip_compile.py` holds the compiled program
  to that).
  History (the forms are in the tree at `01c360e` and before): a
  per-layer scatter with the pools as layer-scan carry measured ~0.05x
  of the HBM roofline on hardware; read-only pools with the fresh row
  folded in analytically and one scatter per token 0.17x; a
  single-launch fused kernel (ISSUE 6) took each pool twice in one
  aliased call, which made XLA copy both whole pools twice per layer,
  84% of the GPT-3 XL step on a v5e (PERF.md section 5, PR 26); a
  layer-folded megakernel (two launches a step) that the v5e compiler
  refused (docs/serving.md).
- **Prefix/radix caching** (default; ``PT_PAGED_PREFIX=0`` disables):
  the page pool doubles as a shared radix store
  (`inference/prefix_cache.py`). ``submit``'s admission looks up the
  longest cached prefix by page-aligned token-hash chain, maps those
  pages into the slot's table READ-ONLY (refcounted; copy-on-write on
  the first partial page when an exact-multiple prompt matches in
  full), and prefills ONLY the suffix — each layer writes the suffix
  KV rows into the slot's pages and attends over [cached prefix +
  suffix causal] via the paged kernel with one query row per suffix
  position. Retirement decrements refcounts instead of freeing;
  refcount-zero prefix pages sit in an LRU and are reclaimed under
  pool pressure.
- **One-pass bucketed prefill**: a prompt attends only to itself
  (causal), so prefill needs NO cache reads — the whole prompt runs
  through the dense forward at a power-of-two bucket and the valid KV
  rows bulk-write into the sequence's pages per page-run. Prompts of
  key/value layers are therefore capped at the largest bucket (512).
- **Layer kinds** (`models/layer_kinds.py`): the engine reads from the
  model's kind what state a layer keeps, how many page pools and of
  what row shape (``page_pools``: ``kp``/``vp`` of (Hkv, page, D) for
  softmax attention; ONE pool of (576, page) latent rows for latent
  attention, which lives in ``state`` with the kind's other pools).
  Softmax attention keeps pages; a kind that keeps a state of fixed
  size per SEQUENCE (power retention) gets a per-slot state pool beside
  the page pool, or in its place (``n_pages`` 0 is legal when no layer
  keeps pages): bound with the slot, zeroed by the first prefill chunk,
  freed with the slot. A kind with ``chunked_prefill`` prefills prompts
  of ANY length up to the context in chunks of ``prefill_chunk`` tokens
  — at most one chunk a step, the last chunk padded and masked; a slot
  still in prefill is not active in the decode step. **A chunk rides in
  the decode step**: ONE program (`_ride_impl`) runs the chunk's rows
  and every slot's decode row through each layer, the kind's prefill
  and step side by side, the feed-forward's weights and each touched
  expert read once for both, so the engine has two programs, the ride
  and the decode step. State
  kinds carry the state from chunk to chunk;
  a kind that keeps PAGES (latent attention) has the whole prompt's
  pages reserved at admission, and a chunk writes its rows into them
  and attends to the pages written before it and to itself. Page
  allocation, table, release and the ``kv_pages`` counters are the same
  for every kind that keeps pages.
- **A stack of more than one feed-forward** (``cfg.leading_dense``):
  the leading dense layers run one by one and the rest as ONE scanned
  body (`_over_layers`); routed experts' matrices reach their kernel as
  the stacks they are (``head["experts"]``), never as a layer cut out.
  Every decode step of such a model also leaves, on the device, what
  the routers of ONE slot (``ROUTING_SLOT``) saw and chose, a row an
  expert layer; ``on_routing(req, position, saw, chose)``, where set,
  fetches them at harvest and is called for every token that slot's
  request fed to a decode step (``saw`` (expert layers, d_model) in
  the model's type, ``chose`` (expert layers, k) int32): what a check
  of the served path's routing reads. Unset, nothing is fetched.
- **Chunked device-side stepping**: like `DecodeEngine`, ``chunk``
  tokens per dispatch with per-slot eos/budget early-stop; pages for
  the whole chunk are reserved up front so the table is static inside
  the dispatch. A kind that prefills in chunks takes one token a
  dispatch: its prompt chunks ride in a step of one.
- **Pipelined dispatch** (``PT_SERVE_INFLIGHT``, default 2): dispatch
  and harvest halves exactly as in `DecodeEngine` — each harvested
  dispatch costs ONE packed device→host transfer (the old `_step_inner`
  materialized lengths, tokens, flags and bads separately), budgets/eos
  ids persist on device, and page reservation runs against a host
  shadow of per-slot lengths (`_host_len` exact at harvest, `_proj_len`
  an upper bound over in-flight dispatches, capped at the request's
  prompt+budget so projection never over-reserves the pool). The page
  table uploads only when a reservation actually grows a table.
  docs/serving.md.

Greedy only (the paged pool is a serving-memory feature; sampling policy
work stays in `DecodeEngine`).
"""

import collections
import math
import os
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.models import gpt as gpt_lib
from paddle_tpu.models import layer_kinds
from paddle_tpu.inference.decode_engine import (Request,
                                                ResilientScheduler,
                                                _Inflight,
                                                _note_retrace,
                                                prompt_lookup_draft,
                                                spec_accept)
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

__all__ = ["PagedDecodeEngine"]

# the slot whose routers' inputs and choices every decode step of a
# model with routed experts brings back (`on_routing`)
ROUTING_SLOT = 0


class _HandoffRequest(Request):
    """A request whose KV state was built on another replica: carries
    the wire pages, the tokens generated so far (one, right after
    prefill; more when a draining replica migrated it mid-decode), and
    the valid-row count until admission installs them
    (``PagedDecodeEngine._admit_handoff``)."""

    __slots__ = ("kv_first", "kv_pages", "kv_wire", "kv_tokens",
                 "kv_ntok")


class PagedDecodeEngine(ResilientScheduler):
    """Continuous-batching greedy generation over a paged KV pool.

        eng = PagedDecodeEngine(model, n_pages=64, max_slots=8)
        r = eng.submit(prompt, max_new_tokens=64, eos_id=2)
        eng.run()                                  # r.tokens

    Status: greedy output is bit-identical to ``gpt.generate`` across
    page/chunk geometries in interpret mode (f32), and serving HBM
    scales with live tokens. On a chip (one v5e, GPT-3 XL; PERF.md has
    the step times) the decode step compiles and serves, agreeing with
    ``gpt.generate`` up to bf16 ties. The step copies no pool (PR 26)
    and its attend walks only the pages a slot holds (PR 30), and an
    admission is one program (PR 34); what bounds it now is the
    weights' read (PERF.md section 5)."""

    on_routing = None       # the module's docstring, "A stack of ..."

    def __init__(self, model, n_pages: int, max_slots: int = 8,
                 page_size: int = 128, steps_per_call: int = 1,
                 buckets=(16, 32, 64, 128, 256, 512),
                 share_weights_with=None, inflight=None,
                 warmup: bool = False, prefix: Optional[bool] = None,
                 prefill_only: bool = False,
                 speculative_k: int = 0,
                 prefill_chunk: int = 512):
        from paddle_tpu import compile_cache
        from paddle_tpu.inference.decode_engine import (
            resolve_engine_weights)
        compile_cache.enable()
        cfg, head, stacked = resolve_engine_weights(model,
                                                    share_weights_with)
        if page_size % 128:
            raise ValueError("page_size must be a multiple of 128")
        self.cfg = cfg
        self.kind = layer_kinds.kind_of(cfg)
        if not self.kind.pages and n_pages:
            raise ValueError(
                f"no layer of this model keeps pages ({self.kind.name} "
                f"layers): n_pages must be 0, got {n_pages}")
        self.S = int(max_slots)
        self.page = int(page_size)
        self.P = int(n_pages)
        self.chunk = int(steps_per_call)
        self.buckets = sorted(b for b in buckets
                              if b <= cfg.max_seq_len)
        for b in self.buckets:
            if b > self.page and b % self.page:
                # the prefill page-run copy slices fixed page windows
                # out of the bucket; a non-dividing page size would
                # clamp the source start and copy the wrong rows
                raise ValueError(
                    f"page_size {self.page} must divide every bucket "
                    f"above it (bucket {b})")
        self._head, self._stacked = head, stacked
        L = cfg.n_layers
        self.prefill_chunk = int(prefill_chunk)
        if self.kind.chunked_prefill and self.chunk != 1:
            raise ValueError(
                f"{self.kind.name} layers prefill in chunks that ride a "
                f"decode step of ONE token: steps_per_call must be 1, got "
                f"{self.chunk}")
        if self.kind.pages and self.kind.chunked_prefill:
            if self.prefill_chunk % self.page:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} must be whole "
                    f"pages of {self.page}: a chunk fills its pages")
        elif cfg.leading_dense:
            raise NotImplementedError(
                "a stack with leading dense layers is served through "
                "the chunked prefill only")
        # layer-folded pools, as the kind shapes them: page p of layer l
        # lives at row l*P + p. ONE extra row at the very end is the
        # scratch page: idle slots' step writes land there instead of
        # corrupting pool page 0 (their padded tables point at page id
        # 0). ``kp``/``vp`` are the pools of keys and values that the
        # one-pass prefill, the prefix cache, the speculative verify and
        # the hand-off address by name (a one-row placeholder for a kind
        # without them); any other page pool is the kind's own and lives
        # in ``state``.
        pools = {name: jnp.zeros(sds.shape, sds.dtype) for name, sds in
                 self.kind.page_pools(cfg, L * self.P + 1,
                                      self.page).items()}
        no_kv = lambda: jnp.zeros(
            (1, cfg.kv_heads, self.page, cfg.head_dim), cfg.dtype)
        self.kp = pools.pop("kp") if "kp" in pools else no_kv()
        self.vp = pools.pop("vp") if "vp" in pools else no_kv()
        self._own_page_pools = tuple(pools)
        self._scratch = L * self.P
        # the kind's own pools, which its chunked prefill and its step
        # update in place: per-sequence state (none for attention),
        # every layer and slot in one array each, and page pools other
        # than kp/vp
        slot_pools = {name: jnp.zeros(sds.shape, sds.dtype) for name, sds
                      in self.kind.slot_state(cfg, self.S).items()}
        self._slot_pools = tuple(slot_pools)
        self.state = dict(slot_pools, **pools)
        # a model with routed experts counts the experts its tokens
        # touched here, on the device; each program's result carries its
        # count back and sets it to zero again
        self._experts = cfg.routed_experts > 0
        self._touched = [0, 0]      # harvested this step: decode, chunks
        if self._experts:
            self.state["experts_touched"] = jnp.zeros((), jnp.int32)
        # slots mid-prompt, oldest admission first: slot -> [prompt,
        # tokens prefilled so far]
        self._prefilling: dict = {}
        self._step_prefill_tokens = self._step_decode_tokens = 0
        from paddle_tpu.ops.pallas.paged_attention import PageAllocator
        self._alloc = PageAllocator(self.P, self.page)
        if self.state and speculative_k:
            raise NotImplementedError(
                f"{self.kind.name} layers are served by the plain decode "
                f"step only (no speculative path)")
        # speculative decode rides the paged step: drafts come from the
        # shared on-device prompt-lookup helper, verify is one per-layer
        # pass at K rows per slot
        self.spec_k = int(speculative_k)
        if self.spec_k and self.spec_k < 2:
            raise ValueError("speculative_k must be >= 2 (one input "
                             "token + at least one candidate)")
        prefix_on = (os.environ.get("PT_PAGED_PREFIX", "1") != "0"
                     if prefix is None else bool(prefix))
        # (a prefix of per-sequence state is not shared: ROADMAP M4)
        # (nor are pages that a chunked prefill fills: ROADMAP M3)
        self._prefix = (PrefixCache(self._alloc, self.page)
                        if prefix_on and self.kind.pages
                        and not self.kind.chunked_prefill else None)
        # disaggregated serving (docs/serving.md): a prefill-only
        # engine admits + prefills but never activates decode — the
        # finished pages leave via detach_handoff; fleet is an optional
        # FleetPrefixDirectory (serving/disagg.py) consulted at
        # admission when the local prefix cache misses
        self.prefill_only = bool(prefill_only)
        if self.prefill_only:
            # role-tagged first-token metric: this engine's "first
            # token" marks the END of prefill, never a client TTFT —
            # fleet-merged serve/ttft_s stays decode-side only
            self._ttft_metric = "serve/prefill_s"
        self.fleet = None
        # pages whose KV arrived over a LOSSY wire (int8/fp8 handoff or
        # fleet fetch): fine to serve and to share locally, but never
        # re-published to the fleet under the original content digest —
        # re-quantizing already-quantized pages would compound the
        # half-step error without bound across hops
        self._lossy_pids: set = set()
        self._tables: List[List[int]] = [[] for _ in range(self.S)]
        # slots evicted for non-finite logits: their pages are scrubbed
        # (zeroed) as they return to the free list (see _release)
        self._tainted: set = set()
        self.lengths = jnp.zeros((self.S,), jnp.int32)
        self.last = jnp.zeros((self.S,), jnp.int32)
        self.active = jnp.zeros((self.S,), bool)
        # budgets / eos ids persist on device across dispatches (set at
        # admission) — pipelined dispatches need no host marshalling
        self.remaining = jnp.zeros((self.S,), jnp.int32)
        self.eos_ids = jnp.full((self.S,), -1, jnp.int32)
        # device-side token history (prompt + generated) feeding the
        # on-device prompt-lookup drafts — speculative only (the plain
        # paged step never reads it)
        self.toks = (jnp.zeros((self.S, cfg.max_seq_len), jnp.int32)
                     if self.spec_k else None)
        self._slot_req: List[Optional[Request]] = [None] * self.S
        self._waiting: collections.deque = collections.deque()
        self.steps = 0
        self.tokens_emitted = 0
        # the pools, the slot vectors and (speculative only, else None)
        # the token history: an admission's one program writes them all
        self._prefill_fn = jax.jit(self._prefill_impl,
                                   donate_argnums=(2, 3, 4, 5))
        self._prefill_sfx_fn = jax.jit(self._prefill_suffix_impl,
                                       donate_argnums=(2, 3, 4, 5))
        self._multi_fn = jax.jit(self._multi_impl,
                                 donate_argnums=(2, 3, 4))
        self._ride_fn = jax.jit(self._ride_impl,
                                donate_argnums=(2, 3, 4, 5, 6, 7))
        # table (arg 4) is NEVER donated: the cached device copy
        # (_table_dev) is reused across dispatches
        self._verify_fn = jax.jit(self._spec_multi_impl,
                                  donate_argnums=(2, 3, 5))
        self._init_pipeline(inflight)
        # host shadows for page reservation: _host_len is the harvested
        # (exact) device length; _proj_len an upper bound including
        # in-flight dispatches (each grows a slot by <= chunk tokens)
        self._host_len = np.zeros((self.S,), np.int64)
        self._proj_len = np.zeros((self.S,), np.int64)
        self._table_dev = None       # cached device page table
        self._table_dirty = True
        self._update_pool_gauges()
        if warmup:
            self.warmup()

    # -- pool bookkeeping ---------------------------------------------------

    @property
    def free_pages(self) -> int:
        return self._alloc.free_pages

    @property
    def kv_bytes(self) -> int:
        """Outstanding KV bytes (pages mapped by slots, both pools) —
        the decode-placement load gauge the disaggregated router reads
        from the heartbeat (membership.heartbeat(load=...))."""
        pools = ([self.state[n] for n in self._own_page_pools]
                 or [self.kp, self.vp])
        per_page = self.cfg.n_layers * sum(
            a.nbytes // a.shape[0] for a in pools)
        return sum(len(t) for t in self._tables) * per_page

    @property
    def state_slots(self) -> int:
        """Sequences holding per-sequence state (bound slots, where
        the layers keep any)."""
        return (sum(r is not None for r in self._slot_req)
                if self._slot_pools else 0)

    @property
    def state_bytes(self) -> int:
        """Bytes of per-sequence state those sequences hold."""
        per_slot = sum(self.state[n].nbytes
                       for n in self._slot_pools) // self.S
        return self.state_slots * per_slot

    def _update_pool_gauges(self):
        from paddle_tpu import stats
        stats.set_value("serve/pool_pages_free", self._alloc.free_pages)
        if self._prefix is not None:
            stats.set_value("serve/pool_pages_shared",
                            self._prefix.shared_pages)
            stats.set_value("serve/pool_pages_cached",
                            self._prefix.cached_pages)

    def _reserve(self, slot: int, n_tokens: int):
        before = len(self._tables[slot])
        tab = self._tables[slot]
        try:
            self._alloc.reserve(tab, n_tokens)
        except MemoryError:
            # pool pressure: reclaim LRU refcount-zero prefix pages
            # (warm cache, not live sequences) before giving up
            need = (n_tokens + self.page - 1) // self.page - len(tab)
            if (self._prefix is None or self._prefix.reclaim(
                    need - self._alloc.free_pages) == 0):
                raise
            self._alloc.reserve(tab, n_tokens)
        if len(tab) != before:
            self._table_dirty = True
            self._update_pool_gauges()

    def _release(self, slot: int):
        tab = self._tables[slot]
        if tab:
            self._table_dirty = True
        scrub: List[int] = []
        if self._prefix is not None:
            # cached (trie-held) pages are refcounted, not freed: at
            # zero they move to the reclaimable LRU with their KV warm.
            # (Filter on the PRE-unref keep set: unref of an invalidated
            # page frees it and drops ownership, and re-testing owns()
            # afterwards would double-release it to the allocator.)
            keep = [p for p in tab if self._prefix.owns(p)]
            for p in keep:
                if self._prefix.unref(p) is not None:
                    scrub.append(p)
            kept = set(keep)
            tab[:] = [p for p in tab if p not in kept]
        if slot in self._tainted:
            # non-finite eviction: the slot's private pages hold KV
            # computed from poisoned activations — scrub them on the
            # way back to the free list, or the nan rows resurface as
            # masked-row residue in whatever sequence reuses the page
            # (additive attention masking keeps nan alive: nan+bias=nan)
            self._tainted.discard(slot)
            scrub.extend(tab)
        self._alloc.release(tab)
        self._lossy_pids.difference_update(tab)
        self._lossy_pids.difference_update(scrub)
        if scrub:
            self._scrub_pages(scrub)
        self._update_pool_gauges()

    def _scrub_pages(self, pids):
        """Zero ``pids``' KV rows in both pools (every layer's view).
        Only the poison path pays this: the pool is recycled without
        zero-on-alloc, so pages freed from a non-finite-evicted slot or
        an invalidated prefix must not carry their nan rows into the
        next sequence that maps them."""
        # ptlint: disable=PT001 -- pids is a host int list (slot table
        # entries); this builds an index upload, never a device sync
        pid_rows = np.asarray(pids, np.int32)[None, :]
        ids = (np.arange(self.cfg.n_layers)[:, None] * self.P
               + pid_rows).ravel()
        if self._own_page_pools:
            for name in self._own_page_pools:
                self.state[name] = self.state[name].at[ids].set(0)
            return
        self.kp = self.kp.at[ids].set(0)
        self.vp = self.vp.at[ids].set(0)

    def _table_array(self) -> jnp.ndarray:
        """(S, max_pages) padded page table at a FIXED width
        (ceil(max_seq_len/page)) so the chunked step never recompiles
        as sequences grow; zeros beyond each slot's pages are never
        dereferenced thanks to the kernel's clamp."""
        mx = (self.cfg.max_seq_len + self.page - 1) // self.page
        out = np.zeros((self.S, mx), np.int32)
        for s, t in enumerate(self._tables):
            out[s, :len(t)] = t
        return jnp.asarray(out)

    def _table_row(self, slot: int):
        """Slot ``slot``'s row of the page table, unfolded, at the
        table's fixed width (numpy: the chunk's dispatch uploads it);
        None where no layer keeps pages."""
        if not self.kind.pages:
            return None
        mx = (self.cfg.max_seq_len + self.page - 1) // self.page
        row = np.zeros((mx,), np.int32)
        tab = self._tables[slot]
        row[:len(tab)] = tab
        return row

    def _table(self) -> jnp.ndarray:
        """The device page table, re-uploaded only when a reservation or
        release actually changed a table — steady-state decode reuses
        the cached device copy instead of paying a host→device transfer
        per dispatch."""
        if self._table_dirty or self._table_dev is None:
            self._table_dev = self._table_array()
            self._table_dirty = False
        return self._table_dev

    # -- jitted bodies ------------------------------------------------------

    def _lm_head(self, head, x):
        x = gpt_lib.final_ln(x, head["lnf_scale"], head["lnf_bias"],
                             self.cfg.norm_eps, self.cfg.rms_norm)
        w = (head["wte"].T if head["lm_head"] is None
             else head["lm_head"])
        return x @ w

    def _over_layers(self, body, carry, head, stacked):
        """``body(carry, (block, layer index)) -> (carry, None)`` over
        the whole stack: the leading dense layers one by one
        (``head["lead"]``: none for most models), then ONE scanned body
        over the stacked rest."""
        lead = head["lead"]
        for i, blk in enumerate(lead):
            carry, _ = body(carry, (blk, jnp.int32(i)))
        carry, _ = lax.scan(
            body, carry,
            (stacked, jnp.arange(len(lead), self.cfg.n_layers)))
        return carry

    def _tail(self, head, blk, i, h, attn, pools):
        """Layer ``i``'s second half on the carry ``(h, pools)``: the
        one-set case of `_tail_sets`."""
        (h,), pools = self._tail_sets(head, blk, i, (h,), (attn,), pools)
        return h, pools

    def _tail_sets(self, head, blk, i, hs, attns, pools):
        """Layer ``i``'s second half on one or more sets of rows
        (`GPTBlock._block_tail_sets`: the feed-forward's weights and each
        touched expert read once for all sets); a model with routed
        experts hands the layer the experts' stacks with its place in
        them, and adds the experts its tokens touched to the count in
        ``pools``; where ``pools`` holds ``routing`` (a decode step's),
        the layer's row of it is filled with what ROUTING_SLOT's router
        saw and chose in the FIRST set (`on_routing`)."""
        if not self._experts:
            return blk._block_tail_sets(hs, attns)[0], pools
        at = i - self.cfg.leading_dense
        hs, touched, routed = blk._block_tail_sets(
            hs, attns, head["experts"] + (at,))
        pools = dict(pools, experts_touched=pools["experts_touched"]
                     + touched)
        if routed is not None and "routing" in pools:
            saw, chose = pools["routing"]
            pools["routing"] = (
                saw.at[at].set(routed[0][ROUTING_SLOT, 0]),
                chose.at[at].set(routed[1][ROUTING_SLOT]))
        return hs, pools

    def _step_inputs(self, head, table, lengths, last):
        """A decode step's input rows (S, 1, d), every slot's pending
        token at its position, and the ``view`` of the page table the
        kind's ``step`` reads (each slot's write page ``base``)."""
        x = jnp.take(head["wte"], last, axis=0)
        if head["wpe"] is not None:
            x = x + jnp.take(head["wpe"], lengths, axis=0)
        x = x[:, None, :]
        pidx = jnp.minimum(lengths // self.page, table.shape[1] - 1)
        view = {"table": table, "n_pages": self.P,
                "scratch": self._scratch,
                "base": jnp.take_along_axis(table, pidx[:, None],
                                            axis=1)[:, 0]}
        return x, view

    def _one_token(self, head, stacked, kp, vp, state, table, lengths,
                   last, active, poison):
        """Advance every active slot one token. Per-slot ``bad`` flags
        non-finite logits (numerical blowup or injected poison) — the
        slot stops advancing and the host evicts only that request.

        Each layer runs its kind's ``step`` (`models/layer_kinds.py`)
        with the pools as the layer scan's carry. Softmax layers call
        `paged_append_attend`: its write launch merges the fresh KV row
        into its pool page in place (the pools are that call's only
        pool operands; inactive slots' writes target the scratch page),
        then its read-only attend runs over the returned pools at
        ``lengths + 1``. No per-token scatter and no pool copy are in
        the dispatch. An inactive slot attends a row nobody wrote (its
        own page's stale row at ``lengths``): its output is finite
        garbage that ``nxt``/``bad`` below mask by ``active``.
        Retention layers call `retention_step`, which updates and
        reads the per-slot state pools the same way (each handed in
        once, aliased in to out) and skips inactive slots."""
        x, view = self._step_inputs(head, table, lengths, last)

        def layer_body(carry, blk_i):
            h, pools = carry
            blk, i = blk_i
            attn, pools = self.kind.step(blk, i, h, lengths, active,
                                         pools, view)
            return self._tail(head, blk, i, h, attn, pools), None

        x, pools = self._over_layers(
            layer_body, (x, dict(state, kp=kp, vp=vp)), head, stacked)
        kp, vp = pools.pop("kp"), pools.pop("vp")
        state = pools
        lengths, nxt, bad = self._sample(self._lm_head(head, x)[:, 0],
                                         poison, active, last, lengths)
        return kp, vp, state, lengths, nxt, bad

    @staticmethod
    def _sample(logits, poison, active, last, lengths):
        """Every slot's greedy token from its logits (S, V): an active
        slot whose logits are not finite (or ``poison``ed) is ``bad``
        and neither advances nor takes a token; the others keep
        ``last`` and their length."""
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        bad = active & ~jnp.all(jnp.isfinite(logits), axis=-1)
        nxt = jnp.argmax(logits.astype(jnp.float32), -1).astype(jnp.int32)
        nxt = jnp.where(active & ~bad, nxt, last)
        lengths = lengths + (active & ~bad).astype(jnp.int32)
        return lengths, nxt, bad

    def _multi_impl(self, head, stacked, kp, vp, state, table, lengths,
                    last, active, remaining, eos, poison):
        """``chunk`` decode steps in one dispatch, per-slot eos/budget/
        non-finite early-stop device-side (pages for the whole chunk are
        reserved before the dispatch, so ``table`` is static here).
        Tokens, emit flags and non-finite flags come back PACKED into
        one (3, chunk, S) int32 array — the lagged harvest pays exactly
        one device→host transfer."""
        _note_retrace("paged_multi")
        touched0 = state.get("experts_touched")
        if self._experts:
            # what ROUTING_SLOT's routers see and choose, a row an
            # expert layer, filled anew by every step
            cfg = self.cfg
            layers = cfg.n_layers - cfg.leading_dense
            state = dict(state, routing=(
                jnp.zeros((layers, cfg.d_model), cfg.dtype),
                jnp.zeros((layers, cfg.experts_per_token), jnp.int32)))

        def one(carry, _):
            kp, vp, state, lengths, last, active, remaining = carry
            kp, vp, state, lengths, nxt, bad = self._one_token(
                head, stacked, kp, vp, state, table, lengths, last,
                active, poison)
            emit = active & ~bad
            remaining = remaining - emit.astype(jnp.int32)
            hit_eos = (nxt == eos) & (eos >= 0)
            active = active & ~bad & ~hit_eos & (remaining > 0)
            return (kp, vp, state, lengths, nxt, active, remaining), \
                (nxt, emit, bad, state.get("routing"))

        (kp, vp, state, lengths, last, active, remaining), \
            (toks, flags, bads, routing) = lax.scan(
                one, (kp, vp, state, lengths, last, active, remaining),
                None, length=self.chunk)
        rows = [toks, flags.astype(jnp.int32), bads.astype(jnp.int32)]
        if self._experts:
            # every step's rows leave with the state (the dispatch
            # takes them off it again: they are a result, not a state)
            state = dict(state, routing=routing)
            # two more rows ride back with the tokens: the experts this
            # dispatch's tokens touched, and those of the prompt chunks
            # since the last dispatch (counted from zero again)
            rows += [jnp.full_like(toks, state["experts_touched"]
                                   - touched0),
                     jnp.full_like(toks, touched0)]
            state = dict(state, experts_touched=jnp.int32(0))
        packed = jnp.stack(rows)
        return kp, vp, state, lengths, last, active, remaining, packed

    def _verify_paged(self, head, stacked, kp, vp, table, lengths,
                      cand, active, poison):
        """One speculative verify over the page pool: K candidate
        tokens per slot in one per-layer pass (batched pool scatter +
        the paged read kernel at one query row per candidate). Returns
        the model's predictions (S, K), the accepted-prefix length
        n_acc (0..K-1) and the per-slot non-finite flag, exactly like
        `DecodeEngine._verify_impl`."""
        S, K = cand.shape
        cfg = self.cfg
        pos = lengths[:, None] + jnp.arange(K)              # (S, K)
        x = jnp.take(head["wte"], cand, axis=0)
        if head["wpe"] is not None:
            x = x + jnp.take(head["wpe"], pos, axis=0)
        scale = 1.0 / math.sqrt(cfg.head_dim)
        mx = table.shape[1]
        pidx = jnp.minimum(pos // self.page, mx - 1)
        pages = jnp.take_along_axis(table, pidx, axis=1)
        offs = (pos % self.page).reshape(-1)
        lens_t = (pos + 1).reshape(-1)

        def layer(carry, blk_i):
            x, kp, vp = carry
            blk, i = blk_i
            q, k, v = blk._qkv(x, lengths)
            rows = jnp.where(active[:, None], i * self.P + pages,
                             self._scratch).reshape(-1)
            kp = kp.at[rows, :, offs, :].set(
                k.reshape(S * K, cfg.kv_heads,
                          cfg.head_dim).astype(kp.dtype))
            vp = vp.at[rows, :, offs, :].set(
                v.reshape(S * K, cfg.kv_heads,
                          cfg.head_dim).astype(vp.dtype))
            o = paged_decode_attention(
                q.reshape(S * K, cfg.n_heads,
                          cfg.head_dim).astype(kp.dtype),
                kp, vp, jnp.repeat(i * self.P + table, K, axis=0),
                lens_t, scale=scale)
            attn = o.astype(x.dtype).reshape(x.shape)
            return (blk._block_tail(x, attn), kp, vp), None

        (x, kp, vp), _ = lax.scan(
            layer, (x, kp, vp), (stacked, jnp.arange(cfg.n_layers)))
        logits = self._lm_head(head, x).astype(jnp.float32)
        logits = jnp.where(poison[:, None, None], jnp.nan, logits)
        bad = ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
        pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        match = jnp.cumprod(
            (cand[:, 1:] == pred[:, :-1]).astype(jnp.int32), axis=1)
        n_acc = jnp.sum(match, axis=1)                      # 0..K-1
        return kp, vp, pred, n_acc, bad

    def _spec_multi_impl(self, head, stacked, kp, vp, table, toks,
                         lengths, last, active, remaining, eos, poison):
        """``chunk`` speculative steps in ONE dispatch over the page
        pool — draft on device (shared prompt-lookup helper), verify K
        candidates per slot, accept via the shared greedy-acceptance
        helper, early-stop per slot on eos/budget. Pages for the whole
        chunk (chunk * K rows) are reserved before the dispatch.
        Packed output (chunk, S, K+2) matches `DecodeEngine`'s spec
        records — the shared scheduler replay applies both."""
        _note_retrace("paged_spec")
        K = self.spec_k

        def one(carry, _):
            kp, vp, toks, lengths, last, active, remaining = carry
            cand = prompt_lookup_draft(toks, lengths, last, K)
            kp, vp, pred, n_acc, bad = self._verify_paged(
                head, stacked, kp, vp, table, lengths, cand, active,
                poison)
            n_eff, last, bad, emitted_eos = spec_accept(
                pred, n_acc, bad, active, remaining, eos, last)
            # history append (same DUS-window idiom as DecodeEngine's
            # spec chunk: garbage beyond n_eff is overwritten or masked
            # by lengths on read; inactive slots rewrite their window)
            for s in range(self.S):
                win = (s, lengths[s] + 1)
                old = lax.dynamic_slice(toks, win, (1, K))
                toks = lax.dynamic_update_slice(
                    toks, jnp.where(active[s], pred[s:s + 1], old), win)
            remaining = remaining - n_eff
            lengths = lengths + n_eff
            active = active & ~bad & ~emitted_eos & (remaining > 0)
            return (kp, vp, toks, lengths, last, active, remaining), \
                (pred, n_eff, bad)

        (kp, vp, toks, lengths, last, active, remaining), \
            (preds, effs, bads) \
            = lax.scan(one, (kp, vp, toks, lengths, last, active,
                             remaining), None, length=self.chunk)
        packed = jnp.concatenate(
            [preds, effs[..., None], bads[..., None].astype(jnp.int32)],
            axis=-1)
        return kp, vp, toks, lengths, last, active, remaining, packed

    def _install_slot(self, vecs, slot, n, nxt, rem0, eos0, final=True):
        """THE write of slot ``slot``'s decode state, traced inside the
        program that sampled its first token ``nxt``: length ``n``, the
        pending token, budget ``rem0``, eos ``eos0`` and the ``active``
        flag, on ``vecs`` = (lengths, last, active, remaining,
        eos_ids). A budget-of-one request (or one whose first token is
        eos) never activates — the device analog of ``_emit`` retiring
        it; a prefill-only engine never decodes. ``final`` (traced in
        the chunked prefill) makes the write conditional: any chunk but
        a prompt's last leaves the slot as it was, and inactive."""
        lengths, last, active, remaining, eos_ids = vecs
        alive = (final & (rem0 > 0) & ((eos0 < 0) | (nxt != eos0))
                 & (not self.prefill_only))
        put = lambda arr, new: arr.at[slot].set(
            jnp.where(final, new, arr[slot]))
        return (put(lengths, n), put(last, nxt),
                active.at[slot].set(alive), put(remaining, rem0),
                put(eos_ids, eos0))

    @staticmethod
    def _seed_history(toks, slot, row, n, nxt):
        """Slot ``slot``'s prompt-lookup history (speculative only):
        ``row`` (a static width) holds the prompt from position 0, its
        first ``n`` entries real; the pending token goes to index
        ``n``. What lies beyond is masked by ``lengths`` on read."""
        width = row.shape[0]
        old = lax.dynamic_slice(toks, (slot, 0), (1, width))
        new = jnp.where(jnp.arange(width) < n, row, old[0])
        toks = lax.dynamic_update_slice(toks, new[None], (slot, 0))
        return toks.at[slot, n].set(nxt)

    def _prefill_impl(self, head, stacked, kp, vp, vecs, toks, tokens,
                      true_len, write_segments, slot, rem0, eos0):
        """One-pass prefill of ONE prompt (1, bucket): the prompt
        attends only to itself (causal), so no cache reads; the valid
        KV rows bulk-write into the sequence's pages per page-run.
        ``write_segments``: (n_seg, L, 3) int32 rows (dst_page_row,
        src_start, run) per layer — page-run copies resolved host-side
        (statically shaped per bucket: n_seg = ceil(bucket/page) + 1,
        padded with run=0). The program also installs the slot's decode
        state (`_install_slot`; ``slot``, ``rem0``, ``eos0`` traced, so
        one program a bucket) and, where ``toks`` is not None, its
        prompt-lookup history from the bucket row: the admission
        dispatches nothing else."""
        _note_retrace("paged_prefill")
        cfg = self.cfg
        x = jnp.take(head["wte"], tokens, axis=0)
        if head["wpe"] is not None:
            x = x + head["wpe"][None, :tokens.shape[1]]

        rows = []

        def layer_body(h, blk):
            attn, rows = self.kind.prefill(blk, h)
            return blk._block_tail(h, attn), rows

        x, (ks, vs) = lax.scan(layer_body, x, stacked)
        # ks: (L, bucket, Hkv, D) -> (L, Hkv, bucket, D); pad the token
        # dim to at least one page so every page-window copy below has a
        # full source window (segments start page-aligned, so windows
        # never straddle the padded end)
        ks = jnp.swapaxes(ks, 1, 2).astype(kp.dtype)
        vs = jnp.swapaxes(vs, 1, 2).astype(vp.dtype)
        if ks.shape[2] < self.page:
            pad = self.page - ks.shape[2]
            ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0)))

        def write_seg(i, kvp):
            kp, vp = kvp

            def write_layer(l, kvp):
                kp, vp = kvp
                dst, src, run = (write_segments[i, l, 0],
                                 write_segments[i, l, 1],
                                 write_segments[i, l, 2])
                # a zero-run segment writes a zero-length slice (no-op
                # via clamped dynamic_slice of size page then masked
                # merge): instead gate on run>0 with lax.cond
                def do(kvp):
                    kp, vp = kvp
                    # run is traced; copy a full page window and merge
                    # the first `run` rows (static window, masked merge)
                    ksrc = lax.dynamic_slice(
                        ks, (l, 0, src, 0),
                        (1, self.cfg.kv_heads, self.page,
                         self.cfg.head_dim))
                    vsrc = lax.dynamic_slice(
                        vs, (l, 0, src, 0),
                        (1, self.cfg.kv_heads, self.page,
                         self.cfg.head_dim))
                    old_k = lax.dynamic_slice(
                        kp, (dst, 0, 0, 0),
                        (1, self.cfg.kv_heads, self.page,
                         self.cfg.head_dim))
                    old_v = lax.dynamic_slice(
                        vp, (dst, 0, 0, 0),
                        (1, self.cfg.kv_heads, self.page,
                         self.cfg.head_dim))
                    m = (jnp.arange(self.page) < run)[None, None, :,
                                                      None]
                    km = jnp.where(m, ksrc, old_k)
                    vm = jnp.where(m, vsrc, old_v)
                    kp2 = lax.dynamic_update_slice(kp, km,
                                                   (dst, 0, 0, 0))
                    vp2 = lax.dynamic_update_slice(vp, vm,
                                                   (dst, 0, 0, 0))
                    return kp2, vp2

                return lax.cond(run > 0, do, lambda kvp: kvp, (kp, vp))

            return lax.fori_loop(0, self.cfg.n_layers, write_layer,
                                 (kp, vp))

        n_seg = write_segments.shape[0]
        kp, vp = lax.fori_loop(0, n_seg, write_seg, (kp, vp))
        idx = jnp.clip(true_len - 1, 0, tokens.shape[1] - 1)
        logits = self._lm_head(head, x[:, idx][:, None])[:, 0]
        nxt = jnp.argmax(logits.astype(jnp.float32), -1).astype(
            jnp.int32)[0]
        vecs = self._install_slot(vecs, slot, true_len, nxt, rem0, eos0)
        if toks is not None:
            toks = self._seed_history(toks, slot, tokens[0], true_len,
                                      nxt)
        return kp, vp, vecs, toks, nxt

    def _prefill_suffix_impl(self, head, stacked, kp, vp, vecs, toks,
                             tokens, sp, true_n, segs, cow_src, cow_dst,
                             table_row, slot, rem0, eos0, prompt_row):
        """Suffix-only prefill over a CACHED prefix (one prompt whose
        first ``sp`` tokens' KV already sit in shared pages mapped into
        ``table_row``). The cached prefix's forward is never recomputed:
        per layer, the suffix tokens' KV rows are written into the
        slot's pages FIRST (page-run segments ``segs``: (pid, dst_off,
        src, run) int32, run=0 padding), then the paged kernel runs with
        ONE QUERY ROW PER SUFFIX POSITION — row t's length is
        ``sp + t + 1``, so it attends over [cached prefix + suffix
        causal] exactly (its own row included, already written).

        ``cow_src``/``cow_dst`` (-1 = none) implement copy-on-write for
        the exact-page-multiple full match: the last matched page is
        copied into a private page before the final token's KV row is
        written inside it.

        tokens: (1, bucket) suffix zero-padded; sp/true_n scalars
        (suffix = prompt[sp:true_n]); table_row: (max_pages,) this
        slot's UNFOLDED page table row. The slot's decode state is
        installed here as in `_prefill_impl`; the history row comes
        from ``prompt_row`` (the WHOLE prompt zero-padded to the largest
        bucket, None unless speculative), the suffix row not holding
        the cached prefix."""
        _note_retrace("paged_prefill_suffix")
        cfg = self.cfg
        bucket = tokens.shape[1]
        L = cfg.n_layers
        scale = 1.0 / math.sqrt(cfg.head_dim)
        mx = table_row.shape[0]

        def do_cow(kvp):
            kp, vp = kvp
            src = jnp.arange(L, dtype=jnp.int32) * self.P + cow_src
            dst = jnp.arange(L, dtype=jnp.int32) * self.P + cow_dst
            return kp.at[dst].set(kp[src]), vp.at[dst].set(vp[src])

        kp, vp = lax.cond(cow_src >= 0, do_cow, lambda kvp: kvp,
                          (kp, vp))

        x = jnp.take(head["wte"], tokens, axis=0)
        if head["wpe"] is not None:
            # per-row clamped gather (not dynamic_slice: its clamped
            # START would shift REAL rows when sp + bucket overruns the
            # table; here only pad rows clamp, and they are unused)
            pos = jnp.clip(sp + jnp.arange(bucket), 0,
                           head["wpe"].shape[0] - 1)
            x = x + jnp.take(head["wpe"], pos, axis=0)[None]

        # row t of the suffix attends over min(sp + t + 1, n) tokens
        lens_t = jnp.minimum(
            sp + 1 + jnp.arange(bucket, dtype=jnp.int32), true_n)
        table_b = jnp.broadcast_to(table_row[None], (bucket, mx))

        def layer_body(carry, blk_i):
            h, kp, vp = carry
            blk, i = blk_i
            q, k, v = blk._qkv(h, jnp.reshape(sp, (1,)))
            # (1, bucket, Hkv, D) -> (Hkv, bucket, D), padded one page
            # on each side so every segment's full-page source window
            # (start = page + src - dst_off) stays in bounds
            ks = jnp.swapaxes(k, 1, 2)[0].astype(kp.dtype)
            vs = jnp.swapaxes(v, 1, 2)[0].astype(vp.dtype)
            ks = jnp.pad(ks, ((0, 0), (self.page, self.page), (0, 0)))
            vs = jnp.pad(vs, ((0, 0), (self.page, self.page), (0, 0)))

            def write_seg(j, kvp):
                kp, vp = kvp
                pid, off, src, run = (segs[j, 0], segs[j, 1],
                                      segs[j, 2], segs[j, 3])
                dst = i * self.P + pid

                def do(kvp):
                    kp, vp = kvp
                    start = self.page + src - off
                    kwin = lax.dynamic_slice(
                        ks, (0, start, 0),
                        (cfg.kv_heads, self.page, cfg.head_dim))
                    vwin = lax.dynamic_slice(
                        vs, (0, start, 0),
                        (cfg.kv_heads, self.page, cfg.head_dim))
                    old_k = lax.dynamic_slice(
                        kp, (dst, 0, 0, 0),
                        (1, cfg.kv_heads, self.page, cfg.head_dim))
                    old_v = lax.dynamic_slice(
                        vp, (dst, 0, 0, 0),
                        (1, cfg.kv_heads, self.page, cfg.head_dim))
                    ar = jnp.arange(self.page)
                    m = ((ar >= off) & (ar < off + run))[None, :, None]
                    km = jnp.where(m, kwin, old_k[0])[None]
                    vm = jnp.where(m, vwin, old_v[0])[None]
                    return (lax.dynamic_update_slice(kp, km,
                                                     (dst, 0, 0, 0)),
                            lax.dynamic_update_slice(vp, vm,
                                                     (dst, 0, 0, 0)))

                return lax.cond(run > 0, do, lambda kvp: kvp, (kp, vp))

            kp, vp = lax.fori_loop(0, segs.shape[0], write_seg,
                                   (kp, vp))
            o = paged_decode_attention(
                q[0].astype(kp.dtype), kp, vp, i * self.P + table_b,
                lens_t, scale=scale)
            attn = o.astype(h.dtype).reshape(h.shape)
            return (blk._block_tail(h, attn), kp, vp), None

        (x, kp, vp), _ = lax.scan(layer_body, (x, kp, vp),
                                  (stacked, jnp.arange(L)))
        idx = jnp.clip(true_n - sp - 1, 0, bucket - 1)
        logits = self._lm_head(head, x[:, idx][:, None])[:, 0]
        nxt = jnp.argmax(logits.astype(jnp.float32), -1).astype(
            jnp.int32)[0]
        vecs = self._install_slot(vecs, slot, true_n, nxt, rem0, eos0)
        if toks is not None:
            toks = self._seed_history(toks, slot, prompt_row, true_n, nxt)
        return kp, vp, vecs, toks, nxt

    def _ride_impl(self, head, stacked, state, lengths, last, active,
                   remaining, eos_ids, table, poison, tokens, pos0,
                   n_valid, slot, final, rem0, eos0, table_row):
        """One chunk (1, prefill_chunk) of slot ``slot``'s prompt and one
        decode token of every active slot, in ONE scan over the layers:
        each layer runs its kind's ``step`` on the (S, 1) decode rows and
        its ``prefill`` on the chunk's rows, then its tail on both sets
        (`_tail_sets`): each set's output projection, residual, norm,
        routing, combine and shared expert as in its own program, the
        feed-forward's weights, dense or the routed experts (ONE kernel
        call over both sets' pairs), read once for ``prefill_chunk + S``
        rows; the head runs once on the decode rows and the chunk's last
        real row. (A tail on the two sets concatenated whole served
        tokens further from the float32 reference on a TPU v5e: XLA made
        the residual sum of the concatenation at float32 for the norm
        and rounded it for the residual: PERF.md, section 7.)

        The chunk: positions from ``pos0``, the first ``n_valid`` tokens
        real. Layers that keep a state per sequence start it at
        position 0 from a zero state (that is how admission zeroes the
        slot) and every other from the slot's own; layers that keep
        pages write the chunk into the slot's pages (``table_row``: the
        slot's row of the page table, None for a kind without pages)
        and attend to the pages before it and to itself. The ``final``
        chunk also samples the first token and installs the slot's
        decode state (`_install_slot`), so admission runs no eager
        program of its own. The decode rows are `_multi_impl`'s one
        step (``table``, ``poison`` as there); a slot that does not
        decode is an inactive row, so ONE program serves every chunk
        of every prompt, with or without slots decoding: the scalars
        are traced. The chunk's slot is never active while it
        prefills, so the two row sets touch disjoint slots. Returns
        the state, the five slot vectors and the packed (rows, 1, S)
        result: the decode rows' as `_multi_impl` packs them, and last
        the chunk's sampled token."""
        _note_retrace("paged_ride")
        xc = jnp.take(head["wte"], tokens, axis=0)
        if head["wpe"] is not None:
            pos = jnp.clip(pos0 + jnp.arange(tokens.shape[1]), 0,
                           head["wpe"].shape[0] - 1)
            xc = xc + jnp.take(head["wpe"], pos, axis=0)[None]
        chunk_view = (None if table_row is None else
                      {"table_row": table_row, "n_pages": self.P,
                       "scratch": self._scratch})
        xd, step_view = self._step_inputs(head, table, lengths, last)
        touched0 = state.get("experts_touched")
        if self._experts:
            cfg = self.cfg
            layers = cfg.n_layers - cfg.leading_dense
            state = dict(state, routing=(
                jnp.zeros((layers, cfg.d_model), cfg.dtype),
                jnp.zeros((layers, cfg.experts_per_token), jnp.int32)))

        def layer_body(carry, blk_i):
            (hd, hc), pools = carry
            blk, i = blk_i
            # (the step first: at Brumby's widths the program then holds
            # 0.14 GB fewer temporaries for the v5e's compiler)
            attn_d, pools = self.kind.step(blk, i, hd, lengths, active,
                                           pools, step_view)
            attn_c, pools = self.kind.prefill(blk, i, hc, pos0, n_valid,
                                              slot, pos0 == 0, pools,
                                              chunk_view)
            # the decode rows first: the routing row filled is theirs
            (hd, hc), pools = self._tail_sets(head, blk, i, (hd, hc),
                                              (attn_d, attn_c), pools)
            return ((hd, hc), pools), None

        (xd, xc), state = self._over_layers(layer_body, ((xd, xc), state),
                                            head, stacked)
        idx = jnp.clip(n_valid - 1, 0, tokens.shape[1] - 1)
        logits = self._lm_head(head, jnp.concatenate(
            [xd, lax.dynamic_slice_in_dim(xc, idx, 1, axis=1)]))[:, 0]
        nxt = jnp.argmax(logits[-1].astype(jnp.float32), -1).astype(
            jnp.int32)
        # the decode rows' step, as `_multi_impl` takes it
        lengths, tok, bad = self._sample(logits[:-1], poison, active, last,
                                         lengths)
        emit = active & ~bad
        remaining = remaining - emit.astype(jnp.int32)
        hit_eos = (tok == eos_ids) & (eos_ids >= 0)
        active = active & ~bad & ~hit_eos & (remaining > 0)
        out = [tok[None], emit[None].astype(jnp.int32),
               bad[None].astype(jnp.int32)]
        if self._experts:
            # as `_multi_impl`'s: the routing rows, a step of them, leave
            # with the state; the experts touched ride back with the
            # tokens and the count starts from zero again
            state = dict(state, routing=jax.tree_util.tree_map(
                lambda a: a[None], state["routing"]))
            out += [jnp.full_like(out[0], state["experts_touched"]
                                  - touched0),
                    jnp.full_like(out[0], touched0)]
            state = dict(state, experts_touched=jnp.int32(0))
        # the chunk's sampled token rides back last (the first token of
        # a prompt's last chunk)
        out.append(jnp.full_like(out[0], nxt))
        vecs = self._install_slot(
            (lengths, tok, active, remaining, eos_ids), slot,
            pos0 + n_valid, nxt, rem0, eos0, final)
        if chunk_view is not None:
            # until its last chunk the slot is not active, and the
            # decode step walks an inactive slot's pages up to its
            # length all the same: none, and not what the slot's last
            # sequence left
            vecs = (vecs[0].at[slot].set(
                jnp.where(final, pos0 + n_valid, 0)),) + vecs[1:]
        return (state, *vecs, jnp.stack(out))

    # -- scheduler ----------------------------------------------------------

    def check_request(self, prompt_len: int, max_new_tokens: int):
        """Admission feasibility (see DecodeEngine.check_request)."""
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if (not self.kind.chunked_prefill
                and prompt_len > self.buckets[-1]):
            raise ValueError(
                f"{self.kind.name} layers prefill a prompt in one pass "
                f"of at most {self.buckets[-1]} tokens (got "
                f"{prompt_len}); only layers that keep per-sequence "
                f"state prefill in chunks")
        if prompt_len + max_new_tokens > self.cfg.max_seq_len:
            raise ValueError("prompt + new tokens exceed max_seq_len")
        if self.spec_k and (prompt_len + max_new_tokens
                            + self.spec_k - 1 > self.cfg.max_seq_len):
            # the last accepted token's verify window wrote K-1 rows
            # past it — those positions must exist in the page table
            raise ValueError(
                f"prompt + new tokens + speculative window "
                f"({prompt_len}+{max_new_tokens}+{self.spec_k - 1}) "
                f"exceed max_seq_len {self.cfg.max_seq_len}")

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               req_id: Optional[str] = None) -> Request:
        import time
        prompt = list(np.asarray(prompt).reshape(-1))
        self.check_request(len(prompt), max_new_tokens)
        req = Request(prompt, max_new_tokens, eos_id,
                      deadline=(None if deadline_s is None
                                else time.monotonic() + deadline_s),
                      rid=req_id)
        self._waiting.append(req)
        return req

    def _free_slot(self) -> Optional[int]:
        for s, r in enumerate(self._slot_req):
            if r is None:
                return s
        return None

    def _on_evict(self, slot: int):
        """Eviction also returns the slot's pages to the pool (the dead
        sequence's memory is reclaimable at once) and stops a prompt
        mid-prefill; the slot's state is zeroed by whoever is admitted
        into it next."""
        self._release(slot)
        self._prefilling.pop(slot, None)
        super()._on_evict(slot)

    def _fail(self, req, reason, slot=None,
              stat="serve/deadline_evictions"):
        if slot is not None and stat == "serve/nonfinite_evictions":
            # a non-finite eviction means this slot's KV is suspect:
            # taint it so _release scrubs its private pages, and drop
            # every trie node its table maps, or a poisoned prefix
            # stays canonical and every future submit of the same
            # (popular) prompt maps the bad pages and fails — forever.
            # Current sharers keep their refs and fail loudly at their
            # own harvest; the next submit prefills cold into scrubbed
            # pages and re-registers a healthy copy.
            self._tainted.add(slot)
            if self._prefix is not None:
                for p in self._tables[slot]:
                    # never frees here — the slot's own mapping keeps
                    # refs >= 1, so the page dies (and is scrubbed)
                    # at this slot's _release via the unref path
                    self._prefix.invalidate(p)
        super()._fail(req, reason, slot, stat)

    def _match_prefix(self, prompt, slot):
        """Longest-cached-prefix lookup at admission: maps the matched
        pages into the slot's (empty) table read-only and returns
        ``(sp, cow_src, chain)`` — the suffix start (tokens served from
        cache), the COW source page (-1 = none), and the prompt's
        digest chain (reused by ``register`` so admission hashes the
        prompt exactly once). An exact-page-multiple full match keeps
        all but the last page: the final token must re-run for
        first-token logits and its KV row lands INSIDE the last matched
        page, so that page is copied to a private one (copy-on-write on
        the first partial page). Counters for the lookup land in
        ``_admit`` AFTER the reservation succeeds — a MemoryError-
        retried admission must not double-count its hit tokens.

        With a fleet directory attached, a LOCAL miss extends through
        the fleet: pages another replica registered are fetched over
        the KV wire, installed into private pages, ADOPTED into the
        local cache (so the retry path and every later submit see them
        as local hits), and the match continues — a prefix warm on any
        replica skips that prefill here too."""
        chain = self._prefix.chain(prompt)
        matched = self._prefix.lookup(prompt, chain=chain)
        if self.fleet is not None and len(matched) < len(chain):
            matched.extend(self._fleet_extend(chain, len(matched)))
        n = len(prompt)
        sp, cow_src = 0, -1
        if matched and len(matched) * self.page >= n:
            cow_src = matched[-1]
            self._prefix.unref(matched[-1])
            matched = matched[:-1]
            sp = n - 1
        elif matched:
            sp = len(matched) * self.page
        self._tables[slot][:] = matched
        if matched:
            self._table_dirty = True
        return sp, cow_src, chain

    def attach_fleet(self, fleet):
        """Wire a ``serving/disagg.FleetPrefixDirectory`` into this
        engine: admission lookups extend through the fleet on a local
        miss, newly-registered prefixes publish, and local
        invalidation/reclaim withdraws fleet-wide (the prefix cache's
        ``on_drop`` hook — BEFORE the freed page can be remapped, so no
        sharer ever fetches a stale digest)."""
        if self._prefix is None:
            raise ValueError("fleet prefix directory needs the local "
                             "prefix cache (PT_PAGED_PREFIX=1)")
        self.fleet = fleet

        def _drop(digest, pid):
            fleet.withdraw(digest)
            self._lossy_pids.discard(pid)

        self._prefix.on_drop = _drop

    def _alloc_one_page(self):
        """One free page for a fleet-fetched prefix, reclaiming LRU
        refcount-zero cache pages under pressure (same policy as
        ``_reserve``); raises MemoryError when the pool is truly
        full."""
        tmp: List[int] = []
        try:
            self._alloc.reserve(tmp, self.page)
        except MemoryError:
            if self._prefix.reclaim(1) == 0:
                raise
            self._alloc.reserve(tmp, self.page)
        return tmp[0]

    def _fleet_extend(self, chain, start):
        """Continue a local prefix match through the fleet directory:
        fetch each next digest's page over the KV wire, install it into
        a private page, adopt it into the local cache (ref'd for this
        admission), stop at the first fleet miss / pool-full. Counters:
        one ``serve/fleet_prefix_lookup`` per consulted admission,
        ``serve/fleet_prefix_hit_tokens`` per page of prefill skipped
        fleet-wide."""
        from paddle_tpu import stats
        got: List[int] = []
        uploads: List[tuple] = []         # (pid, k_page, v_page)
        stats.add("serve/fleet_prefix_lookup")
        for digest in chain[start:]:
            # a stale DESCENDANT may still be canonical locally (its
            # parent was reclaimed; lookup broke at the hole): revive
            # it instead of re-fetching — adopt would refuse it
            pid = self._prefix.revive(digest)
            if pid is not None:
                got.append(pid)
                continue
            try:
                res = self.fleet.fetch(digest)
            except RuntimeError:
                # the wire guard tripped on this fleet page (owner
                # published before its own poison detection, or store
                # corruption): expunge the entry so the fleet heals,
                # and prefill this prefix cold — ONE request pays a
                # cold prefill, the replica never dies of it
                self.fleet.withdraw(digest, force=True)
                res = None
            except TimeoutError:
                res = None              # store hiccup: treat as miss
            if res is None:
                break
            k_page, v_page = res          # (L, 1, Hkv, page, D) host
            try:
                pid = self._alloc_one_page()
            except MemoryError:
                break                     # partial fleet hit is fine
            self._prefix.adopt(digest, pid)
            if self.fleet.wire != "fp32":
                self._lossy_pids.add(pid)
            got.append(pid)
            uploads.append((pid, k_page, v_page))
            stats.add("serve/fleet_prefix_hit_tokens", self.page)
        if uploads:
            # ONE batched pool update per pool for the whole fetch run
            # (each .at[].set materializes a full pool copy — per-page
            # updates would pay 2m copies for m pages)
            L = self.cfg.n_layers
            # ptlint: disable=PT001 -- uploads carries host ints and
            # already-host page arrays; this builds an index upload
            pids = np.asarray([u[0] for u in uploads], np.int32)
            ids = (np.arange(L, dtype=np.int32)[:, None] * self.P
                   + pids[None, :]).ravel()
            ks = np.stack([u[1][:, 0] for u in uploads],
                          axis=1).reshape(ids.size,
                                          *uploads[0][1].shape[2:])
            vs = np.stack([u[2][:, 0] for u in uploads],
                          axis=1).reshape(ids.size,
                                          *uploads[0][2].shape[2:])
            self.kp = self.kp.at[ids].set(jnp.asarray(ks,
                                                      self.kp.dtype))
            self.vp = self.vp.at[ids].set(jnp.asarray(vs,
                                                      self.vp.dtype))
        return got

    def _fleet_publish(self):
        """Publish the pages the LAST ``register`` made newly canonical
        to the fleet directory — content-addressed, so replicas racing
        on the same prefix converge on first-writer-wins."""
        newly = getattr(self._prefix, "last_registered", [])
        for _i, digest, pid in newly:
            if pid in self._lossy_pids:
                continue
            ids = (np.arange(self.cfg.n_layers, dtype=np.int32)
                   * self.P + pid)
            # ptlint: disable=PT001 -- deliberate device→host transfer:
            # this IS the fleet KV-page publication (admission cadence,
            # newly-registered pages only — never steady-state decode)
            k = np.asarray(self.kp[ids])[:, None]
            # ptlint: disable=PT001 -- same deliberate transfer (v pool)
            v = np.asarray(self.vp[ids])[:, None]
            self.fleet.publish(digest, k, v)

    def fleet_republish(self) -> int:
        """Re-publish every live prefix page to the fleet directory —
        the router-failover recovery hook (`serving.router.
        ReplicaSession`): a NEW router generation's store starts empty,
        so without this the fleet-wide prefix warmth this replica
        accumulated would silently vanish. The caller clears the
        directory's published-set first (``fleet.reset_published()``);
        lossy-wire adopted pages stay excluded exactly as in
        `_fleet_publish`. Returns the number of pages re-published."""
        if self.fleet is None:
            return 0
        n = 0
        for digest, pid in list(self._prefix._nodes.items()):
            if pid in self._lossy_pids:
                continue
            ids = (np.arange(self.cfg.n_layers, dtype=np.int32)
                   * self.P + pid)
            # ptlint: disable=PT001 -- deliberate device→host transfer:
            # failover re-publication of the live radix cache (once per
            # router generation — never steady-state decode)
            k = np.asarray(self.kp[ids])[:, None]
            # ptlint: disable=PT001 -- same deliberate transfer (v pool)
            v = np.asarray(self.vp[ids])[:, None]
            self.fleet.publish(digest, k, v)
            n += 1
        return n

    def _corrupt_shared_pages(self, shared):
        """Payload fault site ``paged.shared_page``: with a matching
        nan/bitflip rule installed, corrupt the FIRST shared page this
        admission mapped (all layers) — the blast-radius probe for
        prefix sharing: one poisoned page must fail EVERY sharer loudly
        (each hits the non-finite-logit guard), never silently. Inert
        (one boolean check) without a fault plan."""
        from paddle_tpu.testing import faults
        if not faults.enabled() or not shared:
            return
        ids = np.arange(self.cfg.n_layers) * self.P + shared[0]
        # ptlint: disable=PT001 -- test-only fault injection (gated on
        # faults.enabled()): reading the page back is the point
        page_k = np.asarray(self.kp[ids])
        out = faults.transform("paged.shared_page", page_k)
        if out is page_k:
            # byte-payload actions (bitflip) only fire on bytes values;
            # a nan rule already returned a fresh array above
            buf = page_k.tobytes()
            ob = faults.transform("paged.shared_page", buf)
            if isinstance(ob, (bytes, bytearray)) and bytes(ob) != buf:
                out = np.frombuffer(
                    bytearray(bytes(ob).ljust(len(buf), b"\0")),
                    page_k.dtype).reshape(page_k.shape)
        if out is not page_k:
            self.kp = self.kp.at[ids].set(
                jnp.asarray(out, self.kp.dtype))

    def _admit(self, req: Request, slot: int):
        """Reserve pages and dispatch the one-pass (or suffix-only)
        prefill, which also flips the slot live (`_install_slot`): ONE
        device program an admission, and no sync on the sampled first
        token — it stays on device and rides the harvest queue as a
        'prefill' record, so admission enqueues behind in-flight decode
        dispatches instead of draining them. With the prefix cache on,
        the longest cached prefix's pages are mapped read-only and only
        the suffix is prefilled."""
        from paddle_tpu.observability import trace
        # ptlint: disable=PT001 -- req.prompt is a host int list
        # (submit coerced it); this is an upload, never a sync
        prompt = np.asarray(req.prompt, np.int32)
        n = len(prompt)
        if self.kind.chunked_prefill:
            if self.kind.pages:
                # the prompt's pages, before the span opens (a
                # MemoryError-retried admission leaves no phantom span)
                self._reserve(slot, n)
            with trace.span("serve/admit", slot=slot, prompt=n,
                            bucket=self.prefill_chunk, cached=0,
                            rid=req.rid, programs=0):
                self._admit_chunked(req, slot, prompt)
            return
        sp, cow_src, chain = (self._match_prefix(prompt, slot)
                              if self._prefix is not None
                              else (0, -1, None))
        self._reserve(slot, n)
        bucket = next(b for b in self.buckets if b >= n - sp)
        # the span opens once the reservation HELD (the MemoryError-
        # retried admission re-runs this method and must leave no
        # phantom span) and closes at the _pending.append: the host's
        # preparation and the prefill enqueue, the one device program
        # (`programs`) the admission dispatches
        with trace.span("serve/admit", slot=slot, prompt=n,
                        bucket=bucket, cached=sp, rid=req.rid,
                        programs=1):
            self._admit_reserved(req, slot, prompt, bucket, sp, cow_src,
                                 chain)

    def _admit_chunked(self, req, slot, prompt):
        """Bind ``req`` to ``slot`` and queue its prompt for the chunked
        prefill: nothing is dispatched here (a kind that keeps pages
        had the prompt's reserved by `_admit`). The slot holds state
        from now on; its first chunk starts from zero, and until its
        last chunk it is not active on the device, so no decode step
        touches it."""
        from paddle_tpu.observability import flight, trace
        trace.complete("serve/queue", req.t_submit, rid=req.rid,
                       slot=slot)
        flight.record(req.rid, "admit", slot=slot, prompt=len(prompt),
                      bucket=self.prefill_chunk, cached=0)
        self._slot_req[slot] = req
        self._host_len[slot] = self._proj_len[slot] = 0
        self._disp_rem[slot] = 0
        self._prefilling[slot] = [prompt, 0]

    def _dispatch_ride(self, live):
        """Dispatch the next chunk of the oldest prompt still in prefill
        (at most one chunk a step, so that a long prompt does not stall
        the slots that decode) with the decode token of every slot in
        ``live`` riding in the same program (`_ride_impl`). Its result
        is ONE 'ride' record, replayed as a decode step's, which for a
        prompt's last chunk also brings back the first token (``first``):
        a record of its own would make the harvest wait for the very
        program just dispatched, and the device for the host."""
        from paddle_tpu.observability import trace
        slot, (prompt, done) = next(iter(self._prefilling.items()))
        req = self._slot_req[slot]
        C, n = self.prefill_chunk, len(prompt)
        take = min(C, n - done)
        final = done + take == n
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :take] = prompt[done:done + take]
        rem0 = req.max_new_tokens - 1
        eos0 = -1 if req.eos_id is None else int(req.eos_id)
        self.steps += 1
        self._obs_host_gap()
        with trace.span("serve/prefill_chunk", slot=slot, rid=req.rid,
                        tokens=take, index=done // C, last=final,
                        rode=len(live)):
            (self.state, self.lengths, self.last, self.active,
             self.remaining, self.eos_ids, packed) = self._ride_fn(
                self._head, self._stacked, self.state, self.lengths,
                self.last, self.active, self.remaining, self.eos_ids,
                self._table(), self._poison_mask(), jnp.asarray(tokens),
                jnp.int32(done), jnp.int32(take), jnp.int32(slot),
                jnp.bool_(final), jnp.int32(rem0), jnp.int32(eos0),
                self._table_row(slot))
        self._step_prefill_tokens = take
        for s, _ in live:
            self._proj_len[s] += self._disp_span
        self._finish_dispatch("ride", live, packed)
        self._pending[-1].routing = self.state.pop("routing", None)
        if final:
            del self._prefilling[slot]
            self._host_len[slot] = self._proj_len[slot] = n
            self._disp_rem[slot] = 0 if self.prefill_only else rem0
            self._pending[-1].first = (slot, req)
        else:
            self._prefilling[slot][1] = done + take

    def _admit_reserved(self, req, slot, prompt, bucket, sp, cow_src,
                        chain):
        """The admission past its page reservation (the body of the
        ``serve/admit`` span): prefill ``prompt`` in ``bucket``, its
        first ``sp`` tokens served from the prefix cache
        (``cow_src``/``chain`` as ``_match_prefix`` gave them)."""
        import time
        from paddle_tpu import stats
        from paddle_tpu.observability import flight, trace
        n = len(prompt)
        tab = self._tables[slot]
        if self._prefix is not None:
            if n >= self.page:
                # register this prompt's full pages (private ones
                # become canonical for future hits; already-cached
                # digests skip). NOTE: at this point the pages are
                # still EMPTY for a cold prompt — the prefill dispatch
                # below fills them; fleet publication therefore waits
                # for the dispatched prefill (after the trace.span
                # blocks), reading back only newly-canonical pages.
                self._prefix.register(prompt, tab, chain=chain)
                self._update_pool_gauges()
            # counters only once the reservation held — the
            # MemoryError-retry path re-runs this whole admission
            stats.add("serve/prefix_lookup")
            if sp:
                stats.add("serve/prefix_hit_tokens", sp)
        self._corrupt_shared_pages(tab[:sp // self.page])
        # observability lands only once the reservation HELD — the
        # MemoryError-retried admission re-runs this whole method, and
        # a duplicate serve/queue span would put phantom queue-wait
        # intervals on the stitched per-request lane (same rationale as
        # the prefix counters above)
        trace.complete("serve/queue", req.t_submit, rid=req.rid,
                       slot=slot)
        stats.add("serve/dispatch_launches")
        stats.add("serve/dispatches/prefill")
        flight.record(req.rid, "admit", slot=slot, prompt=n,
                      bucket=bucket, cached=sp)
        rem0 = req.max_new_tokens - 1
        eos0 = -1 if req.eos_id is None else int(req.eos_id)
        # numpy straight into the jitted call: the dispatch uploads the
        # arguments itself, no eager program or `jnp` call per argument
        slot_args = (np.int32(slot), np.int32(rem0), np.int32(eos0))
        vecs = (self.lengths, self.last, self.active, self.remaining,
                self.eos_ids)
        if sp:
            suffix = np.zeros((1, bucket), np.int32)
            suffix[0, :n - sp] = prompt[sp:]
            # page-run plan over positions [sp, n): (pid, dst_off,
            # src-in-suffix, run), run=0 padding
            segs = np.zeros((bucket // self.page + 2, 4), np.int32)
            t, i = sp, 0
            while t < n:
                pid = tab[t // self.page]
                off = t % self.page
                run = min(n - t, self.page - off)
                segs[i] = (pid, off, t - sp, run)
                t += run
                i += 1
            cow_dst = tab[(n - 1) // self.page] if cow_src >= 0 else -1
            mx = (self.cfg.max_seq_len + self.page - 1) // self.page
            row = np.zeros((mx,), np.int32)
            row[:len(tab)] = tab
            prompt_row = None
            if self.spec_k:
                prompt_row = np.zeros((self.buckets[-1],), np.int32)
                prompt_row[:n] = prompt
            with trace.span("serve/dispatch", kind="prefill",
                            bucket=bucket):
                self.kp, self.vp, vecs, self.toks, nxt = \
                    self._prefill_sfx_fn(
                        self._head, self._stacked, self.kp, self.vp,
                        vecs, self.toks, suffix, np.int32(sp),
                        np.int32(n), segs, np.int32(cow_src),
                        np.int32(cow_dst), row, *slot_args, prompt_row)
        else:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = prompt
            # page-run copy plan: valid rows [0, n) at page boundaries,
            # every layer's row of a segment at once
            max_seg = bucket // self.page + 1
            segs = np.zeros((max_seg, self.cfg.n_layers, 3), np.int32)
            layer_rows = np.arange(self.cfg.n_layers) * self.P
            t, i = 0, 0
            while t < n:
                run = min(n - t, self.page - (t % self.page))
                segs[i, :, 0] = layer_rows + tab[t // self.page]
                segs[i, :, 1:] = (t, run)
                t += run
                i += 1
            with trace.span("serve/dispatch", kind="prefill",
                            bucket=bucket):
                self.kp, self.vp, vecs, self.toks, nxt = \
                    self._prefill_fn(
                        self._head, self._stacked, self.kp, self.vp,
                        vecs, self.toks, padded, np.int32(n), segs,
                        *slot_args)
        (self.lengths, self.last, self.active, self.remaining,
         self.eos_ids) = vecs
        if self.fleet is not None and self._prefix is not None \
                and n >= self.page:
            # the prefill dispatch that fills the registered pages is
            # enqueued; publication reads them back (block_until_ready
            # implicit in the host transfer) — newly-canonical only
            self._fleet_publish()
        self._slot_req[slot] = req
        self._host_len[slot] = n
        self._proj_len[slot] = n
        self._disp_rem[slot] = 0 if self.prefill_only else rem0
        self._pending.append(_Inflight("prefill", [(slot, req)], nxt,
                                       time.perf_counter()))

    def _emit(self, slot: int, req: Request, token: int):
        req.tokens.append(token)
        self._obs_first_token(req)
        if self.on_token is not None:
            self.on_token(req, token)
        if ((req.eos_id is not None and token == req.eos_id)
                or len(req.tokens) >= req.max_new_tokens):
            # nothing to write on the device: the program that sampled
            # this token already cleared the slot's `active` (budget or
            # eos: `_install_slot` at prefill, `_multi_impl` /
            # `spec_accept` at decode)
            req.done = True
            self._slot_req[slot] = None
            self._release(slot)
            self._obs_request_end(req)

    # -- disaggregated handoff (docs/serving.md "Disaggregated serving") ----

    def _refuse_state_handoff(self):
        if self.state:
            raise NotImplementedError(
                f"a {self.kind.name} layer's per-sequence state has no "
                f"wire form yet (ROADMAP M4: snapshots of state)")

    def detach_handoff(self, req: Request):
        """Extract a request's KV pages + decode state and retire it
        locally WITHOUT finishing — the sending half of both handoff
        shapes. On a ``prefill_only`` engine the pages hold exactly the
        prompt's KV (the classic prefill→transfer→decode handoff); on
        a decode-capable engine the request may be MID-DECODE (a
        draining replica migrating its in-flight work, ISSUE 16): the
        pipeline drains first, so rows ``[0, lengths)`` hold prompt +
        generated[:-1] and ``meta["tokens"]`` carries every token
        generated so far — the receiver re-emits the last one and
        continues bit-for-bit. Call once ``req.tokens`` is non-empty.

        Returns ``(meta, k, v)``: ``meta`` carries everything
        ``submit_handoff`` needs to reconstruct bit-identical device
        state on the receiving replica (prompt, tokens so far, valid
        row count, remaining budget, eos), ``k``/``v`` are (L, npages,
        Hkv, page, D) host arrays of the slot's pages (tail rows past
        ``n_tokens`` are recycled-pool garbage — the wire codec zeroes
        them; decode overwrites before reading either way)."""
        self._refuse_state_handoff()
        if req.failed:
            raise ValueError(f"request failed before detach: {req.error}")
        if not req.tokens:
            raise ValueError("prefill not harvested yet — pump step() "
                             "until req.tokens holds the first token")
        self._drain()
        if req.done:
            # the drain finished it (budget/eos landed in the pipeline)
            raise ValueError("request completed during drain — publish "
                             "its result directly")
        try:
            slot = self._slot_req.index(req)
        except ValueError:
            raise ValueError("request no longer holds a slot "
                             "(budget-1 requests retire at harvest — "
                             "publish their result directly)")
        n = int(self._host_len[slot])
        npg = (n + self.page - 1) // self.page
        tab = list(self._tables[slot][:npg])
        ids = (np.arange(self.cfg.n_layers, dtype=np.int32)[:, None]
               * self.P + np.asarray(tab, np.int32)[None, :]).ravel()
        L = self.cfg.n_layers
        # ptlint: disable=PT001 -- deliberate device→host transfer: this
        # IS the KV handoff payload leaving the prefill replica
        k = np.asarray(self.kp[ids]).reshape(
            L, npg, self.cfg.kv_heads, self.page, self.cfg.head_dim)
        v = np.asarray(self.vp[ids]).reshape(
            L, npg, self.cfg.kv_heads, self.page, self.cfg.head_dim)
        meta = {"prompt": list(req.prompt), "n_tokens": n,
                "first": int(req.tokens[0]),
                # full generated-so-far history: rows [0, n) hold
                # prompt + tokens[:-1]; the receiver re-emits
                # tokens[-1] (its KV is the next dispatch's write) —
                # [first] right after prefill, longer mid-decode
                "tokens": [int(t) for t in req.tokens],
                "max_new_tokens": int(req.max_new_tokens),
                "eos_id": req.eos_id,
                # trace context rides the handoff: the decode replica's
                # spans for this request carry the SAME rid, so the
                # per-replica trace files stitch into one timeline
                "rid": req.rid}
        from paddle_tpu.observability import flight
        flight.record(req.rid, "handoff-detach", n_tokens=n,
                      pages=npg)
        # retire cleanly: registered prefix pages go warm (they stay
        # published/fleet-canonical on this replica), private ones free
        self._slot_req[slot] = None
        self._release(slot)
        # a mid-decode detach leaves a device-live slot behind:
        # deactivate it so the next dispatch never decodes a ghost
        self.active = self.active.at[slot].set(False)
        self._disp_rem[slot] = 0
        req.done = True
        self._obs_request_end(req)
        return meta, k, v

    def submit_handoff(self, meta: dict, k, v,
                       deadline_s: Optional[float] = None) -> Request:
        """Receiving half of the handoff: enqueue a request whose KV
        state was built elsewhere — right after prefill (the disagg
        pipeline) or mid-decode (a drain migration). Admission (when a
        slot frees) installs the wire pages into this pool and
        reconstructs the exact sender-side device state, so decode
        continues bit-for-bit where the sender stopped (the fp32-wire
        bit-identity contract); the last sender-emitted token rides
        the harvest queue like any local prefill's first token."""
        import time
        self._refuse_state_handoff()
        req = _HandoffRequest(
            meta["prompt"], meta["max_new_tokens"], meta["eos_id"],
            deadline=(None if deadline_s is None
                      else time.monotonic() + deadline_s),
            rid=meta.get("rid"))
        req.kv_first = int(meta["first"])
        req.kv_tokens = [int(t) for t in
                         meta.get("tokens", [meta["first"]])]
        if not req.kv_tokens:
            raise ValueError("handoff meta carries no tokens")
        req.kv_ntok = int(meta.get(
            "n_tokens", len(req.prompt) + len(req.kv_tokens) - 1))
        if req.kv_ntok != len(req.prompt) + len(req.kv_tokens) - 1:
            raise ValueError(
                f"handoff meta inconsistent: n_tokens={req.kv_ntok} "
                f"!= prompt {len(req.prompt)} + generated "
                f"{len(req.kv_tokens)} - 1")
        if len(req.kv_tokens) > req.max_new_tokens:
            raise ValueError("handoff carries more generated tokens "
                             "than its budget")
        req.kv_pages = (np.asarray(k), np.asarray(v))
        # the wire these pages crossed (senders stamp it into the
        # handoff meta); absent → assume lossy, so the pages are never
        # re-published under the original content digest
        req.kv_wire = str(meta.get("wire", "lossy"))
        # NOT check_request: its bucket cap is a PREFILL constraint,
        # and a handoff never prefills here — decode replicas may
        # legitimately run smaller buckets than the prefill tier.
        # What must still hold: a non-empty prompt and a cache window
        # that fits prompt + budget.
        if len(req.prompt) < 1:
            raise ValueError("empty prompt")
        if len(req.prompt) + req.max_new_tokens > self.cfg.max_seq_len:
            raise ValueError("prompt + new tokens exceed max_seq_len")
        # geometry screen HERE (ValueError a serve loop turns into a
        # per-request result): a mismatched fleet config surfacing as a
        # shape error inside a later engine.step() would kill the
        # replica and every other in-flight request on it
        cfg = self.cfg
        n = req.kv_ntok
        want_npg = (n + self.page - 1) // self.page
        repacked = []
        for name, arr in (("k", req.kv_pages[0]), ("v",
                                                   req.kv_pages[1])):
            ok = (arr.ndim == 5 and arr.shape[0] == cfg.n_layers
                  and arr.shape[2] == cfg.kv_heads
                  and arr.shape[4] == cfg.head_dim
                  and arr.shape[1] * arr.shape[3] >= n)
            if not ok:
                raise ValueError(
                    f"handoff {name} pages shaped {tuple(arr.shape)} "
                    f"do not fit this engine's geometry "
                    f"{(cfg.n_layers, want_npg, cfg.kv_heads, self.page, cfg.head_dim)}"
                    " — prefill and decode replicas must share "
                    "(n_layers, kv_heads, head_dim) and carry "
                    "n_tokens rows")
            if arr.shape[1] == want_npg and arr.shape[3] == self.page:
                repacked.append(arr)
                continue
            # cross-geometry sender (different page size, or a dense
            # engine's single page of exactly n rows): flatten to a
            # row stream and repack into THIS pool's page size — the
            # rows are identical, only the blocking differs
            L, H, D = cfg.n_layers, cfg.kv_heads, cfg.head_dim
            rows = arr.transpose(0, 2, 1, 3, 4).reshape(
                L, H, arr.shape[1] * arr.shape[3], D)[:, :, :n, :]
            pad = np.zeros((L, H, want_npg * self.page, D), arr.dtype)
            pad[:, :, :n, :] = rows
            repacked.append(pad.reshape(
                L, H, want_npg, self.page, D).transpose(0, 2, 1, 3, 4))
        req.kv_pages = (repacked[0], repacked[1])
        self._waiting.append(req)
        return req

    def _admit_handoff(self, req: "_HandoffRequest", slot: int):
        """Install transferred pages instead of prefilling: reserve,
        upload the page rows, register the prompt's full pages locally
        (future submits of the same prefix hit them — and publish to
        the fleet like any registration), then reconstruct the device
        state the prefill replica's ``_admit`` would have left. No
        program runs here to carry the slot's install, and a hand-off
        is no steady state: this path, like eviction (`_on_evict`,
        `_fail`), `detach_handoff` and `_scrub_pages`, keeps its eager
        writes."""
        import time
        from paddle_tpu.observability import flight
        n = req.kv_ntok
        flight.record(req.rid, "handoff-install", n_tokens=n,
                      slot=slot, wire=req.kv_wire,
                      generated=len(req.kv_tokens))
        self._reserve(slot, n)
        tab = self._tables[slot]
        k, v = req.kv_pages
        npg = k.shape[1]
        L = self.cfg.n_layers
        # ptlint: disable=PT001 -- tab is a host int list (slot table);
        # this builds an index upload, never a device sync
        tab_arr = np.asarray(tab[:npg], np.int32)
        ids = (np.arange(L, dtype=np.int32)[:, None] * self.P
               + tab_arr[None, :]).ravel()
        self.kp = self.kp.at[ids].set(
            jnp.asarray(k.reshape(ids.size, *k.shape[2:]),
                        self.kp.dtype))
        self.vp = self.vp.at[ids].set(
            jnp.asarray(v.reshape(ids.size, *v.shape[2:]),
                        self.vp.dtype))
        req.kv_pages = None            # free the host copy
        if req.kv_wire != "fp32":
            self._lossy_pids.update(tab[:npg])
        if self._prefix is not None and n >= self.page \
                and n == len(req.prompt):
            # prefix registration only for post-prefill handoffs: a
            # migrated mid-decode slot's tail pages hold GENERATED
            # rows, which must never become prompt-prefix canon
            # ptlint: disable=PT001 -- req.prompt is a host int list
            # (submit coerced it); this is an upload, never a sync
            prompt = np.asarray(req.prompt, np.int32)
            self._prefix.register(prompt, tab)
            self._update_pool_gauges()
            if self.fleet is not None:
                self._fleet_publish()
        # sender-side history replays locally: tokens[:-1] are already
        # final (their KV sits in the installed rows); tokens[-1] is
        # the pending one whose KV the next dispatch writes
        req.tokens = list(req.kv_tokens[:-1])
        nxt = req.kv_tokens[-1]
        rem0 = req.max_new_tokens - len(req.kv_tokens)
        eos0 = -1 if req.eos_id is None else int(req.eos_id)
        alive = rem0 > 0 and (eos0 < 0 or nxt != eos0)
        if self.spec_k:
            # reconstruct the drafting history the sender would hold:
            # prompt + generated[:-1] in rows [0, n), pending token at n
            hist = np.zeros((self.cfg.max_seq_len,), np.int32)
            hist[:len(req.prompt)] = req.prompt
            hist[len(req.prompt):n] = req.kv_tokens[:-1]
            hist[n] = nxt
            self.toks = self.toks.at[slot].set(jnp.asarray(hist))
        self.lengths = self.lengths.at[slot].set(n)
        self.last = self.last.at[slot].set(jnp.int32(nxt))
        self.active = self.active.at[slot].set(bool(alive))
        self.remaining = self.remaining.at[slot].set(rem0)
        self.eos_ids = self.eos_ids.at[slot].set(eos0)
        self._slot_req[slot] = req
        self._host_len[slot] = n
        self._proj_len[slot] = n
        self._disp_rem[slot] = rem0
        # the first token rides the harvest queue exactly like a local
        # prefill's sampled token (replay does _emit(int(payload)))
        self._pending.append(_Inflight("prefill", [(slot, req)],
                                       np.int32(nxt),
                                       time.perf_counter()))

    def step(self) -> int:
        import time
        from paddle_tpu.observability import trace
        t0 = time.perf_counter()
        base = self.tokens_emitted
        self._touched = [0, 0]
        with trace.span("serve/step") as sp:
            n_live = self._step_inner(sp)
            n = self.tokens_emitted - base
            sp.attrs["tokens"] = n
            if sp.live:
                sp.attrs["waiting"] = len(self._waiting)
                # what the traffic holds of the pool, token by token
                # and page by page, at the end of the step
                live = [int(self._host_len[s])
                        for s, r in enumerate(self._slot_req)
                        if r is not None]
                sp.attrs["live_tokens"] = sum(live)
                # the pages those tokens lie on: what the paged attend
                # walks a layer (the table's slots x columns it no
                # longer walks is a constant of the engine)
                sp.attrs["live_pages"] = (
                    sum(-(-n // self.page) for n in live) if self.P
                    else 0)
                sp.attrs["pages_used"] = self.P - self.free_pages
                sp.attrs["pages"] = self.P
                # sequences holding per-sequence state and its bytes;
                # slots mid-prompt; what this step dispatched
                sp.attrs["state_slots"] = self.state_slots
                sp.attrs["state_bytes"] = self.state_bytes
                sp.attrs["prefilling"] = len(self._prefilling)
                sp.attrs["prefill_tokens"] = self._step_prefill_tokens
                sp.attrs["decode_tokens"] = self._step_decode_tokens
                if self._own_page_pools:
                    # cached tokens whose rows the step's attend reads
                    sp.attrs["latent_rows"] = sum(live)
                if self._experts:
                    # token-expert pairs this step dispatched, and the
                    # distinct experts (summed over the expert layers)
                    # that the dispatches harvested in it had touched:
                    # decode steps', and the prompt chunks' before them
                    k = self.cfg.experts_per_token
                    sp.attrs["expert_tokens"] = k * (
                        self._step_prefill_tokens
                        + self._step_decode_tokens)
                    sp.attrs["experts_touched"] = self._touched[0]
                    sp.attrs["experts_touched_prefill"] = self._touched[1]
        if n_live or n:
            # idle polls record nothing (matching DecodeEngine): zero
            # occupancy/queue samples from an empty engine would read
            # as "admission-bound" on the dashboards
            self._obs_step(t0, n, n_live)
        return n

    def _step_inner(self, sp) -> int:
        """One pipeline step — evict (drain boundary), admit, dispatch,
        harvest lag-one. Each harvested dispatch costs exactly ONE
        packed device→host transfer. Returns the live slot count for
        the obs hooks."""
        self._evict_expired()
        self._admit_waiting()
        self._pump(self._dispatch_decode())
        live = sum(r is not None for r in self._slot_req)
        sp.attrs["active"] = live
        return live

    def _admit_waiting(self):
        drained = False
        while self._waiting:
            slot = self._free_slot()
            if slot is None:
                return
            req = self._waiting.popleft()
            try:
                if isinstance(req, _HandoffRequest):
                    self._admit_handoff(req, slot)
                else:
                    self._admit(req, slot)
            except MemoryError:
                # not enough pages right now: return the partial
                # reservation and requeue. Retired pages may be stuck
                # in unharvested dispatches — drain once and retry
                # before falling back to decode-until-room
                self._release(slot)
                self._waiting.appendleft(req)
                if self._pending and not drained:
                    self._drain()
                    drained = True
                    continue
                if not any(r is not None for r in self._slot_req):
                    raise MemoryError(
                        f"page pool ({self.P} pages of {self.page}) too "
                        f"small for even one request of "
                        f"{len(req.prompt)} tokens")
                return

    @property
    def _disp_span(self) -> int:
        """Worst-case per-slot length growth of one decode dispatch:
        ``chunk`` tokens plain, ``chunk * K`` rows speculative (every
        chunk step WRITES K rows at lengths..lengths+K-1 even when
        fewer are accepted)."""
        return self.chunk * max(1, self.spec_k)

    def _reserve_chunk(self, live):
        """Reserve pages for one chunk per live slot against the
        PROJECTED length (host shadow + in-flight growth), capped at
        the request's true maximum (prompt + budget) so projection
        slack never demands pages the request cannot use. Speculative
        dispatches write K rows per step, and the final accepted
        token's verify window pokes up to K-1 rows past the cap — the
        cap stretches by K-1 (check_request guarantees those positions
        exist in the fixed-width table)."""
        for slot, req in live:
            cap = len(req.prompt) + req.max_new_tokens
            if self.spec_k:
                cap += self.spec_k - 1
            need = min(int(self._proj_len[slot]) + self._disp_span + 1,
                       cap)
            self._reserve(slot, need)

    def _dispatch_decode(self) -> bool:
        """This step's one program: where a prompt chunk is due, the
        ride (`_dispatch_ride`) with the live slots' token in it; else
        the decode step of the live slots, if any."""
        from paddle_tpu.observability import trace

        def _live():
            return [(s, r) for s, r in enumerate(self._slot_req)
                    if r is not None and self._disp_rem[s] > 0]

        live = _live()
        try:
            if self.kind.pages and live:
                self._reserve_chunk(live)
        except MemoryError:
            # pool pressure: retired pages may sit in unharvested
            # dispatches — drain, re-anchor the shadows, retry once
            if not self._pending:
                raise
            self._drain()
            live = _live()
            if live:
                self._reserve_chunk(live)
        self._step_decode_tokens = len(live) * self.chunk
        self._step_prefill_tokens = 0
        if self._prefilling:
            self._dispatch_ride(live)
            return True
        if not live:
            return False
        self.steps += 1
        self._obs_host_gap()
        if self.spec_k:
            with trace.span("serve/dispatch", kind="paged_spec",
                            k=self.spec_k, chunk=self.chunk,
                            inflight=len(self._pending)):
                (self.kp, self.vp, self.toks, self.lengths, self.last,
                 self.active, self.remaining, packed) = self._verify_fn(
                    self._head, self._stacked, self.kp, self.vp,
                    self._table(), self.toks, self.lengths, self.last,
                    self.active, self.remaining, self.eos_ids,
                    self._poison_mask())
            kind = "spec"
        else:
            with trace.span("serve/dispatch", kind="paged",
                            chunk=self.chunk,
                            inflight=len(self._pending)):
                (self.kp, self.vp, self.state, self.lengths, self.last,
                 self.active, self.remaining, packed) = self._multi_fn(
                    self._head, self._stacked, self.kp, self.vp,
                    self.state, self._table(), self.lengths, self.last,
                    self.active, self.remaining, self.eos_ids,
                    self._poison_mask())
            kind = "decode"
        for s, _ in live:
            self._proj_len[s] += self._disp_span
        self._finish_dispatch(kind, live, packed)
        self._pending[-1].routing = self.state.pop("routing", None)
        return True

    def _resync_budgets(self, live, cover=None):
        if cover is None:
            cover = self._pending_cover()
        super()._resync_budgets(live, cover)
        for slot, req in live:
            if req.done or self._slot_req[slot] is not req:
                continue
            self._proj_len[slot] = (self._host_len[slot]
                                    + self._disp_span
                                    * cover.get(slot, 0))

    def _replay(self, rec, arr) -> int:
        if rec.first is not None:
            # a prompt's last chunk rode in this program: its first
            # token, as a one-pass prefill's record gives it
            slot, req = rec.first
            if not req.done and self._slot_req[slot] is req:
                self._emit(slot, req, int(arr[-1, 0, 0]))
            self._resync_budgets([rec.first])
        if self._experts and rec.kind != "prefill":
            # the expert counts that rode back with the tokens
            self._touched[0] += int(arr[3, 0, 0])
            self._touched[1] += int(arr[4, 0, 0])
            if self.on_routing is not None:
                self._report_routing(rec, arr)
        return super()._replay(rec, arr)

    def _report_routing(self, rec, arr):
        """`on_routing` for every token that ROUTING_SLOT's request fed
        to this dispatch (before the replay moves its length on)."""
        slot = ROUTING_SLOT
        req = next((r for s, r in rec.live if s == slot), None)
        if req is None or req.done or self._slot_req[slot] is not req:
            return
        saw, chose = jax.device_get(rec.routing)
        at = int(self._host_len[slot])
        for j in range(self.chunk):
            if arr[1, j, slot]:
                self.on_routing(req, at, saw[j], chose[j])
                at += 1

    def _apply_token(self, slot, req, token):
        """Harvested token (shared base replay): emit — which retires
        the request and releases its pages the moment budget/eos hits —
        and advance the exact host length shadow (device lengths grew
        by one for every emitted flag)."""
        self._emit(slot, req, token)
        self._host_len[slot] += 1

    def warmup(self):
        """Pre-trace/compile every (bucket, decode) jitted function on
        throwaway pool mirrors (the pool transiently exists twice) so
        first requests pay no compile latency."""
        import time
        from paddle_tpu import stats
        t0 = time.perf_counter()
        kp, vp = jnp.zeros_like(self.kp), jnp.zeros_like(self.vp)
        state = jax.tree_util.tree_map(jnp.zeros_like, self.state)
        mx = (self.cfg.max_seq_len + self.page - 1) // self.page
        # donated with the mirror pools: COPIES of the slot vectors (the
        # live ones are left as they are) and a mirror of the history
        copy = lambda a: a + 0
        vecs = (copy(self.lengths), copy(self.last), self.active & False,
                copy(self.remaining), copy(self.eos_ids))
        toks = None if self.toks is None else jnp.zeros_like(self.toks)
        if self.kind.chunked_prefill:
            # the ride: a chunk of slot 0 with no slot decoding
            state, *vecs, _ = self._ride_fn(
                self._head, self._stacked, state, *vecs, self._table(),
                self._poison_mask(),
                jnp.zeros((1, self.prefill_chunk), jnp.int32),
                jnp.int32(0), jnp.int32(1), jnp.int32(0),
                jnp.bool_(False), jnp.int32(0), jnp.int32(-1),
                self._table_row(0))
            state.pop("routing", None)
            vecs = tuple(vecs)
        # the arguments in the admission's own form (numpy, uploaded by
        # the dispatch), so that the first request finds the very
        # program: slot 0, a budget of one (never active), no eos
        slot_args = (np.int32(0), np.int32(0), np.int32(-1))
        for b in (self.buckets if self.kind.pages
                  and not self.kind.chunked_prefill else ()):
            segs = np.zeros((b // self.page + 1, self.cfg.n_layers, 3),
                            np.int32)
            kp, vp, vecs, toks, _ = self._prefill_fn(
                self._head, self._stacked, kp, vp, vecs, toks,
                np.zeros((1, b), np.int32), np.int32(1), segs,
                *slot_args)
            if self._prefix is not None:
                # the warm-hit admission path (suffix-only prefill)
                # compiles per bucket too
                sfx_segs = np.zeros((b // self.page + 2, 4), np.int32)
                prompt_row = (np.zeros((self.buckets[-1],), np.int32)
                              if self.spec_k else None)
                kp, vp, vecs, toks, _ = self._prefill_sfx_fn(
                    self._head, self._stacked, kp, vp, vecs, toks,
                    np.zeros((1, b), np.int32), np.int32(0),
                    np.int32(1), sfx_segs, np.int32(-1), np.int32(-1),
                    np.zeros((mx,), np.int32), *slot_args, prompt_row)
        # (the cached no-fault poison mask is made here, not by the
        # first dispatch)
        if self.spec_k:
            out = self._verify_fn(
                self._head, self._stacked, kp, vp, self._table(), toks,
                self.lengths, self.last, self.active, self.remaining,
                self.eos_ids, self._poison_mask())
        else:
            out = self._multi_fn(
                self._head, self._stacked, kp, vp, state, self._table(),
                self.lengths, self.last, self.active, self.remaining,
                self.eos_ids, self._poison_mask())
        jax.block_until_ready(out)
        stats.observe("serve/warmup_s", time.perf_counter() - t0)

    def run(self) -> None:
        while self._waiting or any(r is not None for r in self._slot_req):
            self.step()
        self._drain()   # trailing no-op dispatches (see DecodeEngine.run)

    def dispatch_cost(self, name=None):
        """ISSUE 15 roofline capture for the paged path: AOT
        cost/memory analysis of one paged decode dispatch (the
        speculative verify program when ``speculative_k``) at the
        current pool/table geometry. See DecodeEngine.dispatch_cost."""
        from paddle_tpu.observability import devprof
        if self.spec_k:
            return devprof.capture_jit(
                self._verify_fn, self._head, self._stacked, self.kp,
                self.vp, self._table(), self.toks, self.lengths,
                self.last, self.active, self.remaining, self.eos_ids,
                self._poison_mask(), name=name or "paged_spec")
        return devprof.capture_jit(
            self._multi_fn, self._head, self._stacked, self.kp,
            self.vp, self.state, self._table(), self.lengths, self.last,
            self.active, self.remaining, self.eos_ids,
            self._poison_mask(), name=name or "paged")

    def dispatch_fn_args(self):
        """The decode dispatch's (jitted fn, args) at the current
        geometry (the speculative verify's when ``speculative_k``):
        what `bench.py` and the tests lower to count kernel launches
        without executing, and `chip_smoke.py` compiles."""
        if self.spec_k:
            return (self._verify_fn,
                    (self._head, self._stacked, self.kp, self.vp,
                     self._table(), self.toks, self.lengths, self.last,
                     self.active, self.remaining, self.eos_ids,
                     self._poison_mask()))
        return (self._multi_fn,
                (self._head, self._stacked, self.kp, self.vp,
                 self.state, self._table(), self.lengths, self.last,
                 self.active, self.remaining, self.eos_ids,
                 self._poison_mask()))
