"""Continuous-batching decode engine — the serving workhorse.

Reference analog: the fused cached-decode transformer serving path
(paddle/fluid/operators/fused/fused_multi_transformer_op.cu and its Python
layer python/paddle/incubate/nn/layer/fused_transformer.py:997), which
batches in-flight sequences of different ages into one kernel via a
per-sequence lengths tensor. The TPU re-design keeps that idea — one
program, ragged lengths — and adds the scheduling half the reference
leaves to paddle-serving:

- **Slot-based KV cache**: one preallocated head-major cache
  (L, S, H, T, D) for S slots. Admission assigns a request to a free slot;
  retirement frees it. All shapes are static, so the jitted decode step
  compiles exactly ONCE no matter how requests come and go (the
  no-recompile property tests assert on).
- **Ragged decode step**: every active slot advances one token per step
  at its own cache position. The caches ride the layer scan as READ-ONLY
  xs; each layer emits only its new KV rows (`GPTBlock.decode_rows`,
  which folds the current token's attention contribution in
  analytically), and the rows are written back as S small
  dynamic_update_slices after the scan — the old scan-ys formulation
  made XLA rebuild the entire (L, S, H, T, D) cache every token (~2x
  the cache size in pure copy traffic per step, the dominant overhead
  over the HBM roofline at serving cache lengths).
- **Bucketed chunked prefill**: prompts run through the cached forward in
  power-of-two buckets (bounded compile set); prompts longer than the
  largest bucket stream through it in chunks, and a tail chunk that would
  overrun the cache window slides back over already-written positions
  (deterministic recompute — identical K/V values land in place).
- **Continuous admission**: new requests join between decode steps —
  nothing waits for a "generation batch" to drain.
- **Chunked device-side stepping** (``steps_per_call > 1``): the decode
  loop runs as a lax.scan INSIDE one dispatch, with per-slot eos/budget
  early-stop computed on device; admissions happen between chunks. One
  host round-trip per chunk instead of per token — the serving loop
  belongs on the device (the reference's analog keeps its loop inside
  one CUDA graph).
- **Pipelined dispatch** (``PT_SERVE_INFLIGHT``, default 2): ``step()``
  is split into a dispatch half (enqueue the next jitted call on the
  still-on-device carry) and a harvest half (pull a PREVIOUS dispatch's
  packed results to host). JAX's async dispatch then overlaps the
  host-side bookkeeping of step N with the device execution of step
  N+1 — the eager ``np.asarray`` after every dispatch was the last
  host↔device sync in the hot loop (VERDICT r4 measured decode at ~43%
  of the HBM roofline with the TPU idling on host gaps). Each harvest
  costs exactly ONE transfer: tokens/emit-flags/non-finite flags ride
  one packed int32 array. Request budgets and eos ids live in
  persistent device arrays (``remaining``/``eos_ids``) so consecutive
  dispatches need no host marshalling at all; the host keeps a shadow
  of per-slot budgets only to decide when to stop dispatching.
  Admission rides the pipeline (prefill updates all per-slot device
  state inside the jitted call); deadline eviction — a host-side
  mutation of device state — drains it first. Long prompts' prefill
  chunks interleave with decode dispatches under a per-step token
  budget (``PT_SERVE_PREFILL_TOKENS``), so a long admission no longer
  stalls live slots for its whole prefill. docs/serving.md.
- **Speculative decoding** (``speculative_k > 0``, greedy only): each
  step verifies K candidate tokens per slot in ONE pass, so weights +
  KV prefix are read once per accepted run instead of once per token —
  decode can then beat the per-token HBM roofline. Drafts come from
  prompt-lookup (the last bigram's previous continuation in the slot's
  own history — no draft model) computed ON DEVICE from the engine's
  token-history buffer, and speculative stepping composes with
  ``steps_per_call``: a whole chunk of draft→verify→accept iterations
  runs in one dispatch with per-slot eos/budget early-stop, so the
  host never syncs mid-chunk (per-step host round-trips dominated the
  old implementation on remote PJRT). The scheme is LOSSLESS:
  acceptance keeps exactly the greedy stream of the verify pass's own
  forward math, whatever the acceptance rate (verify and the plain K=1
  step share ONE attention definition, `GPTBlock.decode_rows`). No
  reference analog; the reference decodes strictly one token per
  launch.

HBM note: the engine runs on a scan-stacked copy of the block weights,
passed to its jitted functions as arguments (never closure constants).
While the caller's unstacked `model` stays alive, weights exist twice —
drop the model after constructing the engine if HBM is tight.

`decode_roofline_tokens_per_sec` gives the HBM-bandwidth bound the engine
is judged against (decode reads every weight once per step plus each
active slot's KV prefix).
"""

import collections
import os
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.models import gpt as gpt_lib

__all__ = ["DecodeEngine", "Request", "decode_roofline_tokens_per_sec"]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def resolve_engine_weights(model, share_weights_with):
    """The ONE donor-or-build protocol shared by the contiguous and the
    paged engines: returns (cfg, head dict, scan-stacked blocks). With a
    donor, weights alias the donor's (no second copy); otherwise they
    are built from ``model`` (its blocks are stacked here unless the
    model carries them stacked). A stack whose feed-forward changes
    after ``cfg.leading_dense`` layers keeps those layers as blocks of
    their own under ``head["lead"]`` (empty for every other model) and
    stacks the rest. The routed experts' three matrices, stacked over
    the layers, go under ``head["experts"]`` (None without any) and
    leave empty leaves behind in the stack: the expert kernel takes the
    stacks as they are and a layer's number, where a layer cut out by
    the scan would be a copy of every expert."""
    if model is None:
        if share_weights_with is None:
            raise ValueError(
                "model=None requires share_weights_with (the donor "
                "engine supplies config + weights)")
        cfg = share_weights_with.cfg
    else:
        cfg = model.cfg
        if any(model.blocks[i].moe is not None
               for i in range(cfg.n_layers)):
            raise NotImplementedError(
                "engines serve dense stacks and dropless experts (the "
                "capacity form's decode goes through gpt.generate)")
    if share_weights_with is not None:
        if share_weights_with.cfg is not cfg:
            raise ValueError(
                "share_weights_with engine serves a different model")
        return (cfg, share_weights_with._head,
                share_weights_with._stacked)
    lead = cfg.leading_dense
    head = {"wte": model.wte, "wpe": model.wpe,
            "lnf_scale": model.lnf_scale,
            "lnf_bias": model.lnf_bias,
            "lm_head": model.lm_head,
            "lead": tuple(model.blocks[i] for i in range(lead))}
    # a model whose blocks are already stacked (a state built by
    # init_train_state(stacked=True), or weights loaded that way) is
    # served from that stack: no second copy of every block weight
    stacked = getattr(model, "_stacked_blocks", None)
    if stacked is None:
        stacked = gpt_lib.stack_block_weights(
            [model.blocks[i] for i in range(lead, cfg.n_layers)])
    head["experts"] = None
    if cfg.routed_experts:
        names = ("w_gate", "w_up", "w_down")
        head["experts"] = tuple(getattr(stacked.experts, n) for n in names)
        stacked = stacked.merge_params({
            f"experts.{n}": jnp.zeros((a.shape[0], 0), a.dtype)
            for n, a in zip(names, head["experts"])})
    return cfg, head, stacked


def _note_retrace(fn_name: str):
    """Trace-time (re)trace counter: called at the TOP of the engines'
    jitted bodies, so it runs exactly once per (re)trace and never per
    step — the dynamic complement to ptlint PT002's static retrace
    check. A rising ``compile/retrace/<fn>`` during steady-state
    serving is the recompile leak PT002 can only catch structurally."""
    from paddle_tpu import stats
    # ptlint: disable=PT003 -- deliberate trace-time side effect: the
    # counter must tick when tracing happens, exactly like the
    # collective wrappers' issue-time byte counters (PR 7)
    stats.add("compile/retrace")
    # ptlint: disable=PT003 -- same deliberate trace-time counter
    stats.add(f"compile/retrace/{fn_name}")


def prompt_lookup_draft(toks, lengths, last, K):
    """On-device prompt-lookup drafts, shared by both engines'
    speculative paths: continuation of the most recent earlier
    occurrence of the trailing bigram in the slot's own history — no
    draft model, no host sync. ``toks[s, i]`` is token i for
    i <= lengths[s] (history length lengths+1, pending token at index
    lengths). Returns cand (S, K) with cand[:, 0] = last. Slots
    without a match draft zeros (they still verify+accept the one
    correction token, exactly like the host-draft version)."""
    S, T = toks.shape
    idx = jnp.arange(T)[None, :]
    a = jnp.take_along_axis(
        toks, jnp.maximum(lengths - 1, 0)[:, None], axis=1)[:, 0]
    nxt_t = jnp.concatenate(
        [toks[:, 1:], jnp.zeros((S, 1), jnp.int32)], axis=1)
    ok = ((toks == a[:, None]) & (nxt_t == last[:, None])
          & (idx <= (lengths - 2)[:, None]))
    has = jnp.any(ok, axis=1)
    i_best = jnp.argmax(jnp.where(ok, idx, -1), axis=1)
    offs = (i_best + 2)[:, None] + jnp.arange(K - 1)[None, :]
    vals = jnp.take_along_axis(toks, jnp.clip(offs, 0, T - 1), axis=1)
    valid = offs <= lengths[:, None]   # within history [0, lengths]
    tail = jnp.where(has[:, None] & valid, vals, 0)
    return jnp.concatenate([last[:, None], tail], axis=1)


def spec_accept(pred, n_acc, bad, active, remaining, eos, last):
    """Shared greedy-speculative acceptance: turn one verify's
    predictions (S, K), accepted-prefix counts and non-finite flags
    into the per-slot emitted-token count ``n_eff`` (0..K, after eos
    and budget truncation), the advanced ``last`` token, the
    active-masked ``bad`` flag and the per-slot emitted-eos flag. The
    caller charges ``remaining``/``lengths`` by n_eff and recomputes
    ``active`` — identical math on the contiguous and paged
    engines (the lossless-acceptance contract lives here once)."""
    K = pred.shape[1]
    # inactive slots keep computing from stale state inside the chunk;
    # a non-finite there must not retroactively fail a request that
    # already completed (same mask as the plain-path _one_token)
    bad = bad & active
    n_raw = jnp.where(bad, 0, n_acc + 1)
    # eos truncation: keep tokens up to and including the first eos
    # among the accepted run
    j = jnp.arange(K)[None, :]
    is_eos = ((pred == eos[:, None]) & (eos >= 0)[:, None]
              & (j < n_raw[:, None]))
    any_eos = jnp.any(is_eos, axis=1)
    first_eos = jnp.argmax(is_eos, axis=1)
    n_eff = jnp.where(any_eos, first_eos + 1, n_raw)
    n_eff = jnp.minimum(n_eff, remaining)
    n_eff = jnp.where(active, n_eff, 0)
    new_last = jnp.take_along_axis(
        pred, jnp.maximum(n_eff - 1, 0)[:, None], axis=1)[:, 0]
    last = jnp.where(n_eff > 0, new_last, last)
    emitted_eos = any_eos & (first_eos < n_eff)
    return n_eff, last, bad, emitted_eos


class Request:
    """One in-flight generation request.

    ``deadline`` (monotonic, absolute) bounds the request's wall time in
    the engine; past it the scheduler evicts ONLY this request (slot
    freed, batch peers unaffected) with ``error`` set. ``error`` is also
    set when the non-finite-logit guard evicts a poisoned request —
    callers must check it before trusting ``tokens``.

    ``t_submit``/``t_first`` (perf_counter seconds, set by the engine)
    carry the serving-latency bookkeeping: TTFT = t_first - t_submit
    lands in the ``serve/ttft_s`` histogram (``serve/prefill_s`` on a
    prefill-only engine — the role-tagged split), and the completed
    request's submit→done lifetime is recorded as a ``serve/request``
    trace span.

    ``rid`` is the request's TRACE CONTEXT: the fleet-wide request id
    minted at front-end/router admission and carried through mailbox
    messages, handoff meta, and KV blobs. Every request-scoped span
    attaches it as ``rid=`` so per-replica trace files stitch into one
    cross-process timeline (observability/merge.stitch_trace_files);
    the flight recorder keys its event ring on it too. None for bare
    ``engine.submit()`` callers — spans then carry no rid and the
    request does not stitch (nothing else degrades)."""

    __slots__ = ("prompt", "max_new_tokens", "eos_id", "tokens", "done",
                 "deadline", "error", "t_submit", "t_first",
                 "_obs_ended", "rid")

    def __init__(self, prompt, max_new_tokens, eos_id, deadline=None,
                 rid=None):
        import time
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.tokens: List[int] = []   # generated only
        self.done = False
        self.deadline = deadline      # absolute time.monotonic() budget
        self.error: Optional[str] = None
        self.t_submit = time.perf_counter()
        self.t_first: Optional[float] = None
        self._obs_ended = False
        self.rid = rid

    @property
    def ttft_s(self) -> Optional[float]:
        """Queue wait + prefill up to the first generated token."""
        return (None if self.t_first is None
                else self.t_first - self.t_submit)

    @property
    def failed(self) -> bool:
        return self.error is not None

    @property
    def output(self) -> List[int]:
        return self.prompt + self.tokens


class _Inflight:
    """One in-flight dispatch awaiting harvest: the live (slot, request)
    snapshot it covered, the packed on-device result array, and the
    dispatch timestamp. ``kind`` is 'prefill' (payload: the sampled
    first token), 'decode' (packed (3, chunk, S): tokens / emit flags /
    non-finite flags), 'ride' (the paged engine's decode rows that rode
    in a prompt chunk's program: replayed as 'decode') or 'spec'
    (packed (chunk, S, K+2))."""

    __slots__ = ("kind", "live", "payload", "t", "routing", "first")

    def __init__(self, kind, live, payload, t):
        self.kind, self.live, self.payload, self.t = kind, live, payload, t
        self.routing = None     # paged engine, a model with routed experts
        # paged engine, a 'ride' that carried a prompt's last chunk: its
        # (slot, request), whose first token the payload's last row holds
        self.first = None


class _HandoffRequest(Request):
    """A request whose KV state was built on another replica (a drain
    migration landing on a slot-contiguous engine): carries the wire
    KV rows, the tokens generated so far, and the valid-row count
    until admission installs them (``DecodeEngine._admit_handoff``)."""

    __slots__ = ("kv_rows", "kv_tokens", "kv_ntok", "kv_wire")


class ResilientScheduler:
    """Shared degradation bookkeeping for the serving engines: evict ONE
    request (deadline overrun or non-finite logits) without disturbing
    its batch peers. Engines override `_on_evict` to reclaim their own
    per-slot resources (the paged engine returns the slot's pages).

    Also the shared serving-observability surface (docs/observability.md
    ``serve/*``): per-request TTFT and lifetime, per-step queue depth and
    batch occupancy, per-token latency — the numbers a serving operator
    scrapes to answer "what is p99 TTFT and are we admission-bound".

    Service hooks (the continuous-batching front-end in
    ``paddle_tpu/serving/scheduler.py`` installs these; docs/serving.md
    "Front-end"):

    - ``on_token(req, token)`` — called the moment a harvested token is
      appended to ``req.tokens`` (streaming APIs fan tokens out from
      here; token order matches the request's stream exactly).
    - ``on_retire(req)`` — called exactly once when a request leaves
      the engine (retired, deadline-evicted, or poison-evicted; check
      ``req.error``). Fires from inside ``step()``'s harvest, i.e. the
      moment the slot frees — a front-end backfills the empty slot
      here so the next dispatch is never under-occupied.
    - ``bucket_policy(engine, remaining)`` — overrides prefill bucket
      selection (DecodeEngine's chunked prefill): return a bucket size
      from ``engine.buckets`` for a prefill chunk covering
      ``remaining`` prompt tokens. None keeps the built-in choice
      (smallest covering bucket)."""

    on_token = None
    on_retire = None
    bucket_policy = None
    # speculative depth (0 = off): engines that support speculative
    # decode set this in their ctor; the shared replay unpacks 'spec'
    # records (chunk, S, K+2) by it
    spec_k = 0
    # role-tagged first-token metric: a prefill-only engine's "first
    # token" is the END of prefill, not a client-visible TTFT — it
    # records serve/prefill_s instead (the paged ctor overrides), so
    # fleet-merged serve/ttft_s holds ONLY decode-side end-to-end
    # samples (the PR 12 bench pre-mark workaround, retired)
    _ttft_metric = "serve/ttft_s"

    @property
    def free_slots(self) -> int:
        """Slots with no request bound (admission capacity right now)."""
        return sum(r is None for r in self._slot_req)

    @property
    def queued(self) -> int:
        """Requests submitted but not yet assigned a slot."""
        return len(self._waiting)

    @property
    def kv_bytes(self) -> int:
        """Outstanding KV bytes across live slots — the load gauge
        role-aware routing places decode work by. Slot-contiguous
        engines charge the full per-slot cache window per live slot;
        the paged engine overrides with pages actually held."""
        live = sum(r is not None for r in self._slot_req)
        cfg = self.cfg
        per_slot = (2 * cfg.n_layers * cfg.kv_heads * self.T
                    * cfg.head_dim * np.dtype(self.kc.dtype).itemsize)
        return live * per_slot

    def _on_evict(self, slot: int):
        self.active = self.active.at[slot].set(False)

    def _fail(self, req: Request, reason: str, slot: Optional[int] = None,
              stat: str = "serve/deadline_evictions"):
        from paddle_tpu import stats
        from paddle_tpu.observability import flight
        req.done = True
        req.error = reason
        if slot is not None:
            self._slot_req[slot] = None
            self._on_evict(slot)
            self._disp_rem[slot] = 0
        stats.add(stat)
        # terminal failure: dump the request's flight record NOW — the
        # postmortem (which bucket, which evictions, which handoff
        # hops) must not require a re-run under tracing
        flight.record(req.rid, "evicted", reason=reason, stat=stat,
                      slot=slot, tokens=len(req.tokens))
        flight.dump(req.rid, reason)
        self._obs_request_end(req)

    # -- pipelined dispatch (shared by both engines) ------------------------
    def _init_pipeline(self, inflight):
        """In-flight depth (how many dispatches may be enqueued before
        the oldest is harvested): ctor arg beats PT_SERVE_INFLIGHT beats
        the default 2. Depth 1 is the fully synchronous baseline the
        bit-identity tests compare against."""
        depth = (int(inflight) if inflight is not None
                 else int(os.environ.get("PT_SERVE_INFLIGHT", "2")))
        if depth < 1:
            raise ValueError(f"in-flight depth must be >= 1, got {depth}")
        self.depth = depth
        self._pending: collections.deque = collections.deque()
        # host shadow of per-slot dispatch budgets: how many more tokens
        # are worth dispatching for, given the dispatches already in
        # flight. Decides ONLY when to stop dispatching — the truth
        # (remaining/eos/active) lives on device.
        self._disp_rem = np.zeros((self.S,), np.int64)
        self._t_disp_end: Optional[float] = None

    def _pending_cover(self):
        """slot -> number of in-flight DECODE dispatches covering it."""
        cover: dict = {}
        for rec in self._pending:
            if rec.kind != "prefill":
                for s, _ in rec.live:
                    cover[s] = cover.get(s, 0) + 1
        return cover

    def _resync_budgets(self, live, cover=None):
        """Re-anchor the host budget shadow to harvested truth: the
        request's true remaining minus the guaranteed progress (at
        least ``chunk`` tokens each) of dispatches still in flight.
        Exact for the plain/chunked paths; a safe lower bound for
        speculative (whose per-dispatch yield varies), where a few
        no-op dispatches at the tail are bounded by the depth."""
        if cover is None:
            cover = self._pending_cover()
        for slot, req in live:
            if req.done or self._slot_req[slot] is not req:
                continue
            rem = req.max_new_tokens - len(req.tokens)
            self._disp_rem[slot] = max(
                0, rem - self.chunk * cover.get(slot, 0))

    def _obs_host_gap(self):
        """Host-side bubble between finishing one dispatch enqueue and
        issuing the next — the time the device risks idling on the host
        at depth 1; the pipeline's job is to hide it."""
        import time
        from paddle_tpu import stats
        if self._t_disp_end is not None:
            stats.observe("serve/host_gap_s",
                          time.perf_counter() - self._t_disp_end)

    def _finish_dispatch(self, kind, live, payload):
        """Post-enqueue bookkeeping shared by both engines: charge the
        budget shadows, queue the in-flight record, stamp the gap
        timer, publish the gauge and the per-path launch counters (the
        launch-tax numbers ROADMAP item 1's r06 recapture needs
        attributable on-chip: serve/dispatch_launches total plus
        serve/dispatches/<kind>)."""
        import time
        from paddle_tpu import stats
        for s, _ in live:
            self._disp_rem[s] = max(0, self._disp_rem[s] - self.chunk)
        self._pending.append(_Inflight(kind, live, payload,
                                       time.perf_counter()))
        self._t_disp_end = time.perf_counter()
        stats.add("serve/dispatch_launches")
        stats.add(f"serve/dispatches/{kind}")
        stats.set_value("serve/inflight", len(self._pending))

    def _pump(self, dispatched: bool):
        """The harvest policy: keep at most ``depth`` dispatches in
        flight after a dispatch (depth 1 = fully synchronous), pop one
        when there was nothing to dispatch (drain tail). An idle step
        also resets the host-gap timer so traffic gaps never pollute
        serve/host_gap_s."""
        if dispatched:
            while len(self._pending) >= self.depth:
                self._harvest_one()
        else:
            self._t_disp_end = None
            if self._pending:
                self._harvest_one()

    def _harvest_one(self) -> int:
        """Pull the OLDEST in-flight dispatch's packed results to host
        (ONE transfer) and replay them into Requests. While the
        transfer blocks, younger dispatches keep the device busy — that
        overlap is the pipeline's entire win."""
        from paddle_tpu import stats
        from paddle_tpu.observability import trace
        rec = self._pending.popleft()
        with trace.span("serve/harvest", kind=rec.kind,
                        inflight=len(self._pending)) as sp:
            # the ONE place the host blocks on the device; the rest of
            # serve/harvest is the host's replay of what came back
            with trace.span("serve/device_wait", kind=rec.kind):
                # ptlint: disable=PT001 -- THE one deliberate sync: the
                # lag-one harvest's single packed device→host transfer
                # (docs/serving.md)
                arr = np.asarray(rec.payload)
            emitted = self._replay(rec, arr)
            sp.attrs["tokens"] = emitted
        stats.set_value("serve/inflight", len(self._pending))
        self.tokens_emitted += emitted
        return emitted

    def _drain(self):
        """Harvest every in-flight dispatch — the hard pipeline
        boundary: a host-side mutation of device state (deadline
        eviction) must see fully-applied results first."""
        while self._pending:
            self._harvest_one()

    def _replay(self, rec, arr) -> int:
        """Apply one harvested dispatch's packed results to its live
        snapshot ('prefill', 'decode' and 'spec' records — both engines
        dispatch the same record kinds, so the replay lives here once).
        Requests retired or evicted since the dispatch are skipped —
        the device had already deactivated their slots, so their flags
        in ``arr`` are all False. Engines customize via ``_apply_token``
        (what one emitted token does) and ``_after_replay`` (post-loop
        retirement)."""
        if rec.kind == "prefill":
            slot, req = rec.live[0]
            if not req.done and self._slot_req[slot] is req:
                # the prefill's sampled token is the first generated one
                self._emit(slot, req, int(arr))
            self._resync_budgets(rec.live)
            return 0
        if rec.kind == "spec":
            return self._replay_spec(rec, arr)
        toks = arr[0]
        flags = arr[1].astype(bool)
        bads = arr[2].astype(bool)
        total = 0
        for slot, req in rec.live:
            if req.done or self._slot_req[slot] is not req:
                continue
            for j in range(self.chunk):
                if flags[j, slot] and not req.done:
                    self._apply_token(slot, req, int(toks[j, slot]))
                    total += 1
            if bads[:, slot].any() and not req.done:
                self._fail(req, "non-finite logits", slot=slot,
                           stat="serve/nonfinite_evictions")
        self._after_replay(rec)
        self._resync_budgets(rec.live)
        return total

    def _replay_spec(self, rec, arr) -> int:
        """Speculative records unpack (chunk, S, K+2): K predictions,
        the accepted count n_eff, the non-finite flag — the first
        n_eff predictions of each chunk step are the emitted tokens."""
        K = self.spec_k
        preds, effs = arr[..., :K], arr[..., K]
        bads = arr[..., K + 1].astype(bool)
        total = 0
        for slot, req in rec.live:
            if req.done or self._slot_req[slot] is not req:
                continue
            for j in range(self.chunk):
                for t in range(int(effs[j, slot])):
                    self._apply_token(slot, req, int(preds[j, slot, t]))
                    total += 1
            if bads[:, slot].any():
                self._fail(req, "non-finite logits", slot=slot,
                           stat="serve/nonfinite_evictions")
        self._after_replay(rec)
        self._resync_budgets(rec.live)
        return total

    def _apply_token(self, slot: int, req: Request, token: int):
        raise NotImplementedError

    def _after_replay(self, rec):
        pass

    def drain(self) -> None:
        """Block until every in-flight dispatch is harvested and applied
        (the pipeline analog of jax.block_until_ready). Request state
        (``tokens``/``done``) is exact after this returns."""
        self._drain()

    # -- serving metrics (shared by both engines) ---------------------------
    def _obs_first_token(self, req: Request):
        """Called at the request's FIRST generated token. Role-tagged:
        decode-capable engines record ``serve/ttft_s``; a prefill-only
        engine records ``serve/prefill_s`` (its first token marks the
        end of prefill, and a prefill-side sample in the TTFT histogram
        would halve the fleet's effective p99)."""
        import time
        from paddle_tpu import stats
        if req.t_first is None:
            req.t_first = time.perf_counter()
            stats.observe(self._ttft_metric, req.t_first - req.t_submit)

    def _obs_request_end(self, req: Request):
        """Request left the engine (done or evicted): close its span —
        an after-the-fact submit→now interval on the rank timeline —
        and record its TPOT (decode-phase per-token latency, the SLO
        bench's second axis next to TTFT). Idempotent: eviction and
        retirement may both see the request. The ``on_retire`` service
        hook fires here (same exactly-once guard)."""
        import time
        from paddle_tpu import stats
        from paddle_tpu.observability import trace
        if req._obs_ended:
            return
        req._obs_ended = True
        now = time.perf_counter()
        if req.t_first is not None and len(req.tokens) > 1:
            stats.observe("serve/tpot_s",
                          (now - req.t_first) / (len(req.tokens) - 1))
        trace.complete("serve/request", req.t_submit,
                       rid=req.rid, prompt=len(req.prompt),
                       tokens=len(req.tokens), error=req.error)
        if (req.t_first is not None
                and self._ttft_metric == "serve/ttft_s"):
            # the request's DECODE phase (first token → end) as its own
            # rid-tagged span: the stitched per-request lane's decode
            # segment (prefill-only engines have no decode phase)
            trace.complete("serve/decode", req.t_first, rid=req.rid,
                           tokens=len(req.tokens))
        if self.on_retire is not None:
            self.on_retire(req)

    def _obs_step(self, t0: float, emitted: int, live: int):
        """Per-step serving telemetry: queue depth / batch occupancy
        histograms and the per-token latency histogram (step wall time
        amortized over the tokens it emitted)."""
        import time
        from paddle_tpu import stats
        stats.observe("serve/queue_depth", len(self._waiting))
        stats.observe("serve/batch_occupancy", live / max(1, self.S))
        if emitted > 0:
            stats.observe("serve/token_s",
                          (time.perf_counter() - t0) / emitted)

    def _evict_expired(self):
        """Deadline sweep (queue + live slots) run at each step entry.
        Evicting a LIVE slot mutates device state mid-pipeline (active
        flags, the paged engine's pages), so the pipeline drains first:
        in-flight results are applied, then whatever is still expired
        is evicted. Queued evictions touch no device state and need no
        drain."""
        import time
        now = time.monotonic()
        for req in [r for r in self._waiting
                    if r.deadline is not None and now > r.deadline]:
            self._waiting.remove(req)
            # distinct from the mid-decode counter: a queue reject
            # wasted no device work, an eviction abandoned some — the
            # admission-control dashboards must tell them apart
            self._fail(req, "deadline exceeded while queued",
                       stat="serve/queue_deadline_rejects")
        if any(req is not None and req.deadline is not None
               and now > req.deadline for req in self._slot_req):
            self._drain()
            now = time.monotonic()
            for slot, req in enumerate(self._slot_req):
                if (req is not None and req.deadline is not None
                        and now > req.deadline):
                    self._fail(req, "deadline exceeded", slot=slot)

    def _poison_mask(self):
        """Injection mask for this dispatch (site engine.poison_logits).
        With no fault plan installed this returns one cached all-False
        device array — the production hot path pays no per-step host
        allocation or transfer."""
        from paddle_tpu.testing import faults
        if not faults.enabled():
            mask = getattr(self, "_no_poison", None)
            if mask is None:
                mask = self._no_poison = jnp.zeros((self.S,), bool)
            return mask
        return jnp.asarray(faults.slot_mask("engine.poison_logits",
                                            self.S))


class DecodeEngine(ResilientScheduler):
    """Continuous-batching generation over a dense GPT model.

        eng = DecodeEngine(model, max_slots=8, max_len=512)
        r1 = eng.submit(prompt_a, max_new_tokens=32)
        r2 = eng.submit(prompt_b, max_new_tokens=8)   # joins mid-flight
        eng.run()                                     # drains everything
        r1.tokens, r2.tokens

    Greedy by default; temperature/top-k/top-p mirror `gpt.generate`.
    Pass ``mesh`` (a tp-axis Mesh) for tensor-parallel serving: weights
    place per PARTITION_RULES, caches shard over heads, and GSPMD
    partitions the jitted bodies (≙ HybridParallelInference).
    """

    def __init__(self, model, max_slots: int = 8,
                 max_len: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 temperature: float = 0.0, top_p: float = 1.0,
                 top_k: int = 0, seed: int = 0, cache_dtype=None,
                 speculative_k: int = 0, steps_per_call: int = 1,
                 share_weights_with: "Optional[DecodeEngine]" = None,
                 weight_dtype: Optional[str] = None, mesh=None,
                 inflight: Optional[int] = None, warmup: bool = False,
                 prefill_tokens: Optional[int] = None):
        from paddle_tpu import compile_cache
        compile_cache.enable()
        cfg, head, stacked = resolve_engine_weights(model,
                                                    share_weights_with)
        self.cfg = cfg
        # prefer a 128-multiple cache length (keeps the flash-decode kernel
        # engaged) but never exceed the position table — jnp.take would
        # clamp out-of-range positions silently
        cap = cfg.max_seq_len
        self.T = min(_round_up(min(max_len or cap, cap), 128), cap)
        self.S = int(max_slots)
        self.sample = (float(temperature), float(top_p), int(top_k))
        if buckets is None:
            buckets = [b for b in (16, 32, 64, 128, 256, 512)
                       if b <= self.T] or [self.T]
        self.buckets = sorted(set(int(b) for b in buckets))
        self._bucket_set = set(self.buckets)
        if self.buckets[-1] > self.T:
            raise ValueError(
                f"bucket {self.buckets[-1]} exceeds cache length {self.T}")

        # the weights the jitted bodies actually touch: the embedding /
        # final-ln / head leaves, and ONE scan-stacked copy of the
        # blocks (passed as arguments, so nothing is baked into
        # executables). A second engine over the same model shares the
        # stacked copy via share_weights_with — at 1.3B a redundant
        # copy is 2.4GB of HBM (resolved by resolve_engine_weights).
        self._head, self._stacked = head, stacked
        if weight_dtype == "int8":
            # weight-only int8 serving: decode is HBM-bandwidth bound,
            # so halving the dominant read (block matmul weights stream
            # as int8, dequantized per-tile at the MXU) raises
            # throughput toward 2x the bf16 roofline. Per-(layer,
            # out-channel) scales; embeddings / norms / the (tied) LM
            # head stay in float. Composes with share_weights_with:
            # the quantized copy is built FROM the shared stack without
            # mutating the donor's.
            self._quantize_stacked_int8()
        elif weight_dtype is not None:
            raise ValueError(
                f"weight_dtype must be None or 'int8', "
                f"got {weight_dtype!r}")
        self.mesh = mesh
        if mesh is not None:
            if share_weights_with is not None:
                raise NotImplementedError(
                    "mesh + share_weights_with: the placement would "
                    "duplicate the shared stack on the mesh — place one "
                    "engine and share FROM it instead")
            if weight_dtype is not None:
                raise NotImplementedError("mesh + weight_dtype")

        dt = cache_dtype or cfg.dtype
        shape = (cfg.n_layers, self.S, cfg.kv_heads, self.T,
                 cfg.head_dim)
        self.kc = jnp.zeros(shape, dt)
        self.vc = jnp.zeros(shape, dt)
        self.lengths = jnp.zeros((self.S,), jnp.int32)
        self.last = jnp.zeros((self.S,), jnp.int32)
        self.active = jnp.zeros((self.S,), bool)
        # device-side token history (prompt + generated, one row per
        # slot): toks[s, i] is token i for i <= lengths[s] (the pending
        # `last` token sits at index lengths[s]). Feeds the on-device
        # prompt-lookup drafts — speculative stepping never syncs the
        # host mid-chunk.
        self.toks = jnp.zeros((self.S, self.T), jnp.int32)
        # per-slot token budgets + eos ids as PERSISTENT device state:
        # set by the prefill dispatch, decremented by the decode
        # dispatches — consecutive dispatches need no host marshalling,
        # which is what lets them pipeline
        self.remaining = jnp.zeros((self.S,), jnp.int32)
        self.eos_ids = jnp.full((self.S,), -1, jnp.int32)
        if mesh is not None:
            self._place_on_mesh(model, mesh)
        self._rng = jax.random.PRNGKey(seed)

        self._slot_req: List[Optional[Request]] = [None] * self.S
        self._waiting: collections.deque = collections.deque()

        self.spec_k = int(speculative_k)
        if self.spec_k:
            if self.spec_k < 2:
                raise ValueError("speculative_k must be >= 2 (one input "
                                 "token + at least one candidate)")
            if temperature != 0.0:
                raise NotImplementedError(
                    "speculative decoding is greedy-only (lossless "
                    "acceptance needs argmax determinism)")
        self.chunk = int(steps_per_call)
        if self.chunk < 1:
            raise ValueError("steps_per_call must be >= 1")
        self.steps = 0          # device round-trips (the spec-decode win)
        self.tokens_emitted = 0

        # caches donated: the engine rebinds them every call, and donation
        # lets XLA update the multi-GB buffers in place. The plain path
        # is the chunk=1 instance of _multi_impl — every decode dispatch
        # goes through it (or the speculative wrapper), so eos/budget
        # early-stop always lives on device and results always come
        # back as one packed array.
        self._multi_fn = jax.jit(self._multi_impl, donate_argnums=(2, 3))
        self._prefill_fn = jax.jit(self._prefill_impl,
                                   donate_argnums=(2, 3, 4))
        self._verify_fn = jax.jit(self._spec_multi_impl,
                                  donate_argnums=(2, 3, 4))

        self._init_pipeline(inflight)
        self._admitting: collections.deque = collections.deque()
        if prefill_tokens is None:
            prefill_tokens = int(os.environ.get(
                "PT_SERVE_PREFILL_TOKENS", "0")) or self.buckets[-1]
        # per-step prompt-token budget for interleaved prefill (at least
        # one bucket so an open admission always progresses)
        self._prefill_budget = max(int(prefill_tokens), self.buckets[0])
        if warmup:
            self.warmup()

    def _place_on_mesh(self, model, mesh):
        """Tensor-parallel serving (≙ HybridParallelInference,
        fleet/utils/hybrid_parallel_inference.py): place the stacked
        weights per PARTITION_RULES (leading layer axis replicated) and
        the KV caches head-sharded over 'tp'; GSPMD then partitions the
        jitted decode bodies and inserts the attention/MLP psums. Only
        the 'tp' axis may exceed 1 — slots stay whole so admission's
        per-slot cache slicing never crosses a shard boundary."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        shape = dict(mesh.shape)
        tp = shape.get("tp", 1)
        extra = {k: v for k, v in shape.items() if k != "tp" and v > 1}
        if extra:
            raise ValueError(
                f"DecodeEngine mesh supports a tp axis only, got {extra}")
        if self.cfg.n_heads % tp or self.cfg.kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide heads "
                f"({self.cfg.n_heads}/{self.cfg.kv_heads})")
        sleaves, treedef, specs = gpt_lib.stacked_partition_specs(
            self._stacked, model.blocks[0])
        placed = [jax.device_put(
            leaf, NamedSharding(mesh, gpt_lib.mesh_safe_spec(spec, mesh)))
            for leaf, spec in zip(sleaves, specs)]
        self._stacked = jax.tree_util.tree_unflatten(treedef, placed)
        self._head = {
            k: (None if v is None else jax.device_put(
                jnp.asarray(v),
                NamedSharding(mesh, gpt_lib.mesh_safe_spec(
                    gpt_lib.partition_spec(k), mesh))))
            for k, v in self._head.items()}
        kv_spec = NamedSharding(mesh, P(None, None, "tp", None, None))
        self.kc = jax.device_put(self.kc, kv_spec)
        self.vc = jax.device_put(self.vc, kv_spec)
        rep = NamedSharding(mesh, P())
        self.lengths = jax.device_put(self.lengths, rep)
        self.last = jax.device_put(self.last, rep)
        self.active = jax.device_put(self.active, rep)
        self.toks = jax.device_put(self.toks, rep)
        self.remaining = jax.device_put(self.remaining, rep)
        self.eos_ids = jax.device_put(self.eos_ids, rep)

    def _quantize_stacked_int8(self):
        """Replace the stacked blocks' matmul weights with int8
        QuantTensors (symmetric absmax, per-layer-per-output-channel
        scales). The QuantTensor rides the block pytree in the weight's
        registered slot, so the scanned layer body sees a per-layer
        (in, out) int8 weight and its ``x @ w`` routes through
        QuantTensor.__rmatmul__ (Pallas int8 matmul on TPU)."""
        from paddle_tpu.quantization import QuantTensor
        # rebuild the Module object first (leaves shared, container
        # fresh) so a stack borrowed via share_weights_with is never
        # mutated under the donor engine
        stacked = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self._stacked),
            jax.tree_util.tree_leaves(self._stacked))
        self._stacked = stacked
        for name in ("wqkv", "wo", "wup", "wdown"):
            w = getattr(stacked, name, None)
            if w is None or isinstance(w, QuantTensor):
                continue
            wf = jnp.asarray(w).astype(jnp.float32)   # (L, in, out)
            absmax = jnp.max(jnp.abs(wf), axis=1, keepdims=True)
            scale = jnp.maximum(absmax, 1e-8) / 127.0
            q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
            object.__setattr__(stacked, name,
                               QuantTensor(q, scale, w.dtype))

    # -- jitted bodies ------------------------------------------------------

    def _lm_head(self, head, x):
        """Final LN + (tied) LM projection on (S, L, d) → (S, L, V)."""
        x = gpt_lib.final_ln(x, head["lnf_scale"], head["lnf_bias"])
        w = (head["wte"].T if head["lm_head"] is None
             else head["lm_head"])
        return x @ w

    def _write_rows(self, kc, vc, k_rows, v_rows, lengths, active):
        """Write each ACTIVE slot's K new KV rows at its own cache
        position: S small dynamic_update_slices on the carried buffers
        instead of the full-cache rebuild the old scan-ys formulation
        paid (~2x the cache size in copy traffic per step).

        An INACTIVE slot rewrites its existing row (a read-select-write
        identity — the contiguous analog of the paged engine's scratch
        page): its device ``lengths`` is stale, and with interleaved
        admission a decode dispatch enqueued between prefill chunks
        would otherwise clobber a prompt row the prefill already wrote.

        k_rows/v_rows: (L, S, K, Hkv, D) stacked layer outputs."""
        kr = jnp.transpose(k_rows, (0, 1, 3, 2, 4))   # (L, S, Hkv, K, D)
        vr = jnp.transpose(v_rows, (0, 1, 3, 2, 4))
        for s in range(self.S):
            pos = lengths[s]
            win = (0, s, 0, pos, 0)
            old_k = lax.dynamic_slice(kc, win, kr[:, s:s + 1].shape)
            old_v = lax.dynamic_slice(vc, win, vr[:, s:s + 1].shape)
            kc = lax.dynamic_update_slice(
                kc, jnp.where(active[s], kr[:, s:s + 1], old_k), win)
            vc = lax.dynamic_update_slice(
                vc, jnp.where(active[s], vr[:, s:s + 1], old_v), win)
        return kc, vc

    def _one_token(self, head, stacked, kc, vc, lengths, last, active,
                   rng, poison):
        """Advance every active slot one token: the shared body of the
        single-step and chunked-step entry points. The caches ride the
        layer scan as READ-ONLY xs; each layer emits only its new KV
        rows (`GPTBlock.decode_rows`), written back in one batch after
        the scan.

        Degradation guard: per-slot ``bad`` flags any non-finite logits
        (a poisoned request — NaN/Inf from a numerical blowup or fault
        injection via ``poison``). A bad slot emits nothing and does not
        advance; the host evicts only that request from the batch."""
        temperature, top_p, top_k = self.sample
        x = jnp.take(head["wte"], last, axis=0)
        if head["wpe"] is not None:   # rope models position in attention
            x = x + jnp.take(head["wpe"], lengths, axis=0)
        x = x[:, None, :]

        def layer(x, blk_kv):
            blk, k_l, v_l = blk_kv
            y, k_rows, v_rows = blk.decode_rows(
                x, (k_l, v_l), lengths,
                allow_kernel=self.mesh is None)
            return y, (k_rows, v_rows)

        x, (k_rows, v_rows) = lax.scan(layer, x, (stacked, kc, vc))
        kc, vc = self._write_rows(kc, vc, k_rows, v_rows, lengths,
                                  active)
        logits = self._lm_head(head, x)[:, 0]
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        bad = active & ~jnp.all(jnp.isfinite(logits), axis=-1)
        rng, k = jax.random.split(rng)
        nxt = gpt_lib._sample_token(logits.astype(jnp.float32), k,
                                    temperature, top_p, top_k)
        nxt = jnp.where(active & ~bad, nxt, last)
        lengths = lengths + (active & ~bad).astype(jnp.int32)
        return kc, vc, lengths, nxt, rng, bad

    def _multi_impl(self, head, stacked, kc, vc, lengths, last, active,
                    remaining, eos, rng, poison):
        """``chunk`` decode steps in ONE dispatch (lax.scan over
        _one_token), with per-slot early stop device-side: a slot stops
        advancing when it hits its eos id or exhausts its token budget,
        and a slot whose logits go non-finite stops emitting immediately
        (its ``bad`` flag tells the host to evict the request).

        Serving loops belong on the device — host dispatch latency
        otherwise bounds tokens/sec regardless of model speed. The
        reference's analog is the fused-multi-transformer loop staying
        inside one CUDA graph. Emits the (chunk, S) tokens, emit flags
        and non-finite flags PACKED into one int32 array so the lagged
        harvest pays exactly one device→host transfer."""
        _note_retrace("decode_multi")

        def one(carry, _):
            kc, vc, lengths, last, active, remaining, rng = carry
            kc, vc, lengths, nxt, rng, bad = self._one_token(
                head, stacked, kc, vc, lengths, last, active, rng, poison)
            emit = active & ~bad
            remaining = remaining - emit.astype(jnp.int32)
            hit_eos = (nxt == eos) & (eos >= 0)
            active = active & ~bad & ~hit_eos & (remaining > 0)
            return (kc, vc, lengths, nxt, active, remaining, rng), \
                (nxt, emit, bad)

        (kc, vc, lengths, last, active, remaining, rng), \
            (toks, flags, bads) = \
            lax.scan(one, (kc, vc, lengths, last, active, remaining, rng),
                     None, length=self.chunk)
        packed = jnp.stack([toks, flags.astype(jnp.int32),
                            bads.astype(jnp.int32)])
        return (kc, vc, lengths, last, active, remaining, rng, packed)

    def _verify_impl(self, head, stacked, kc, vc, lengths, cand, active,
                     poison):
        """One speculative verify: K candidate tokens per slot through
        one pass. Returns the model's predictions (S, K), the
        accepted-prefix length n_acc (0..K-1), and the per-slot
        non-finite ``bad`` flag; the chunked wrapper applies eos/budget
        truncation and advances the state."""
        S, K = cand.shape
        x = jnp.take(head["wte"], cand, axis=0)
        if head["wpe"] is not None:
            x = x + jnp.take(head["wpe"],
                             lengths[:, None] + jnp.arange(K), axis=0)

        def layer(x, blk_kv):
            blk, k_l, v_l = blk_kv
            y, k_rows, v_rows = blk.decode_rows(
                x, (k_l, v_l), lengths,
                allow_kernel=self.mesh is None)
            return y, (k_rows, v_rows)

        x, (k_rows, v_rows) = lax.scan(layer, x, (stacked, kc, vc))
        kc, vc = self._write_rows(kc, vc, k_rows, v_rows, lengths,
                                  active)
        logits = self._lm_head(head, x).astype(jnp.float32)  # (S, K, V)
        logits = jnp.where(poison[:, None, None], jnp.nan, logits)
        bad = ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
        pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # candidate j (cand[:, j], j>=1) is accepted iff it equals the
        # model's prediction at the previous position — cumulative
        match = jnp.cumprod(
            (cand[:, 1:] == pred[:, :-1]).astype(jnp.int32), axis=1)
        n_acc = jnp.sum(match, axis=1)                 # 0..K-1
        return kc, vc, pred, n_acc, bad

    def _draft_device(self, toks, lengths, last):
        """On-device prompt-lookup drafts — the shared module-level
        `prompt_lookup_draft` at this engine's K (the paged engine's
        speculative path drafts through the same helper)."""
        return prompt_lookup_draft(toks, lengths, last, self.spec_k)

    def _spec_multi_impl(self, head, stacked, kc, vc, toks, lengths,
                         last, active, remaining, eos, poison):
        """``chunk`` speculative steps in ONE dispatch: draft on device
        from the history buffer, verify K candidates per slot in one
        pass, accept the longest greedy-matching run, early-stop per
        slot on eos/budget — the host never syncs mid-chunk (the old
        one-step-per-dispatch version paid 2+ host round-trips per
        verify).

        Emits the (chunk, S, K) predictions, (chunk, S) accepted counts
        and non-finite flags packed into ONE (chunk, S, K+2) int32
        array — one transfer per lagged harvest."""
        _note_retrace("decode_spec")
        K = self.spec_k

        def one(carry, _):
            kc, vc, toks, lengths, last, active, remaining = carry
            cand = self._draft_device(toks, lengths, last)
            kc, vc, pred, n_acc, bad = self._verify_impl(
                head, stacked, kc, vc, lengths, cand, active, poison)
            n_eff, last, bad, emitted_eos = spec_accept(
                pred, n_acc, bad, active, remaining, eos, last)
            # history append: pred[j] is the token at absolute position
            # lengths+1+j. All K values are written (garbage beyond
            # n_eff is overwritten by the next step's window or masked
            # by lengths on read); at the very end of a slot's budget
            # the window can touch [T-K, T) via DUS clamping — the slot
            # is retiring, its history is never read again. INACTIVE
            # slots rewrite their existing window (same guard as
            # _write_rows): a mid-admission slot's stale lengths would
            # otherwise clobber prompt history a prefill chunk already
            # wrote, corrupting the prompt-lookup drafts.
            for s in range(self.S):
                win = (s, lengths[s] + 1)
                old = lax.dynamic_slice(toks, win, (1, K))
                toks = lax.dynamic_update_slice(
                    toks, jnp.where(active[s], pred[s:s + 1], old), win)
            remaining = remaining - n_eff
            lengths = lengths + n_eff
            active = active & ~bad & ~emitted_eos & (remaining > 0)
            return (kc, vc, toks, lengths, last, active, remaining), \
                (pred, n_eff, bad)

        (kc, vc, toks, lengths, last, active, remaining), \
            (preds, effs, bads) \
            = lax.scan(one, (kc, vc, toks, lengths, last, active,
                             remaining), None, length=self.chunk)
        packed = jnp.concatenate(
            [preds, effs[..., None], bads[..., None].astype(jnp.int32)],
            axis=-1)
        return (kc, vc, toks, lengths, last, active, remaining, packed)

    def _prefill_impl(self, head, stacked, kc, vc, toks, lengths, last,
                      active, remaining, eos_ids, slot, tokens, start,
                      true_total, is_final, rem0, eos0, rng):
        """Run one prompt chunk through the slot's cache slice; on the
        final chunk, sample the first generated token, activate the
        slot, and install its token budget (``rem0``, the budget net of
        this first token) and eos id into the persistent device arrays
        — so decode dispatches already enqueued behind this prefill
        pick the slot up with NO host round-trip. `tokens` is
        (1, bucket) — one compile per bucket size. The chunk is also
        recorded in the device history buffer (the speculative path
        drafts from it). Returns the sampled token as an extra output;
        the scheduler harvests it lag-one like any other dispatch."""
        _note_retrace("decode_prefill")
        cfg = self.cfg
        L, bucket = cfg.n_layers, tokens.shape[1]
        sl = (L, 1, cfg.kv_heads, self.T, cfg.head_dim)
        kcs = lax.dynamic_slice(kc, (0, slot, 0, 0, 0), sl)
        vcs = lax.dynamic_slice(vc, (0, slot, 0, 0, 0), sl)

        x = jnp.take(head["wte"], tokens, axis=0)
        if head["wpe"] is not None:
            x = x + lax.dynamic_slice_in_dim(head["wpe"], start, bucket)

        def layer(x, blk_kv):
            blk, k_l, v_l = blk_kv
            x, (k_l, v_l) = blk.forward_cached(x, (k_l, v_l), start)
            return x, (k_l, v_l)

        x, (kcs, vcs) = lax.scan(layer, x, (stacked, kcs, vcs))
        kc = lax.dynamic_update_slice(kc, kcs, (0, slot, 0, 0, 0))
        vc = lax.dynamic_update_slice(vc, vcs, (0, slot, 0, 0, 0))

        idx = jnp.clip(true_total - 1 - start, 0, bucket - 1)
        logits = self._lm_head(head, x[:, idx][:, None])[:, 0]
        temperature, top_p, top_k = self.sample
        rng, k = jax.random.split(rng)
        nxt = gpt_lib._sample_token(logits.astype(jnp.float32), k,
                                    temperature, top_p, top_k)[0]
        # history: the prompt chunk at [start, start+bucket) (zero pads
        # beyond the prompt are never read), and on the final chunk the
        # pending first generated token at index true_total
        toks = lax.dynamic_update_slice(toks, tokens, (slot, start))
        toks = jnp.where(
            is_final,
            lax.dynamic_update_slice(toks, nxt.reshape(1, 1),
                                     (slot, true_total)), toks)
        onehot = jnp.arange(self.S) == slot
        upd = jnp.logical_and(onehot, is_final)
        # a request whose whole budget was the first token, or whose
        # first token IS its eos, never activates — the device-side
        # analog of the host _emit retiring at admission
        alive = jnp.logical_and(
            rem0 > 0, jnp.logical_or(eos0 < 0, nxt != eos0))
        lengths = jnp.where(upd, true_total, lengths)
        last = jnp.where(upd, nxt, last)
        active = jnp.logical_or(active, jnp.logical_and(upd, alive))
        remaining = jnp.where(upd, rem0, remaining)
        eos_ids = jnp.where(upd, eos0, eos_ids)
        return (kc, vc, toks, lengths, last, active, remaining, eos_ids,
                rng, nxt)

    # -- scheduler ----------------------------------------------------------

    def check_request(self, prompt_len: int, max_new_tokens: int):
        """Admission feasibility check WITHOUT enqueueing (the serving
        front-end rejects infeasible requests at its API edge instead
        of surfacing the error from a later pump). Raises ValueError."""
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if prompt_len + max_new_tokens > self.T:
            raise ValueError(
                f"{prompt_len} prompt + {max_new_tokens} new tokens "
                f"exceed cache length {self.T}")
        if self.spec_k and (prompt_len + max_new_tokens
                            + self.spec_k - 1 > self.T):
            raise ValueError(
                f"speculative window: prompt + new + K-1 "
                f"({prompt_len}+{max_new_tokens}+{self.spec_k - 1}) "
                f"exceed cache length {self.T}")

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               req_id: Optional[str] = None) -> Request:
        """``deadline_s``: wall-time budget for this request (queue wait
        included). A request past its deadline is evicted alone — the
        batch keeps serving its peers. ``req_id`` is the fleet-wide
        trace context (front-end/router request id) carried into every
        request-scoped span and flight-recorder event."""
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

    def _admit_next(self) -> bool:
        """Move the next waiting request into a free slot as an
        INCREMENTAL prefill job: its chunks dispatch under the per-step
        token budget, interleaved with decode dispatches, so a long
        prompt no longer stalls live slots for its whole prefill."""
        import time
        from paddle_tpu.observability import flight, trace
        slot = self._free_slot()
        if slot is None or not self._waiting:
            return False
        req = self._waiting.popleft()
        # the queue-wait phase ends HERE: the stitched per-request lane
        # derives queue-wait from submission to prefill start
        trace.complete("serve/queue", req.t_submit, rid=req.rid,
                       slot=slot)
        if isinstance(req, _HandoffRequest):
            # no prefill to run: install the transferred rows directly
            self._admit_handoff(req, slot)
            return True
        flight.record(req.rid, "admit", slot=slot,
                      prompt=len(req.prompt))
        self._slot_req[slot] = req      # reserve; decode skips it until
        self._disp_rem[slot] = 0        # the final chunk flips it live
        self._admitting.append({
            "req": req, "slot": slot, "start": 0,
            # ptlint: disable=PT001 -- req.prompt is a host int list
            # (submit coerced it); this is an upload, never a sync
            "prompt": np.asarray(req.prompt, np.int32),
            "t0": time.perf_counter()})
        return True

    def _dispatch_prefill_chunk(self, job):
        """Dispatch ONE bucket-sized prompt chunk. On the final chunk
        the jitted body flips the slot live on device (lengths / last /
        active / remaining / eos_ids) and the sampled first token rides
        the harvest queue as a 'prefill' record. Returns (bucket tokens
        consumed, finished)."""
        import time
        from paddle_tpu import stats
        from paddle_tpu.observability import flight, trace
        req, slot = job["req"], job["slot"]
        prompt, start = job["prompt"], job["start"]
        total = len(prompt)
        remaining = total - start
        if self.bucket_policy is not None:
            bucket = int(self.bucket_policy(self, remaining))
            if bucket not in self._bucket_set:
                raise ValueError(
                    f"bucket_policy returned {bucket}, not one of "
                    f"{self.buckets}")
        else:
            bucket = next((x for x in self.buckets if x >= remaining),
                          self.buckets[-1])
        s0 = start
        if s0 + bucket > self.T:
            # tail window would overrun the cache: slide it back over
            # already-prefilled positions — same tokens at the same
            # positions recompute the identical K/V, so the overlapped
            # rewrite is a no-op and the write stays in bounds
            s0 = self.T - bucket
        n = min(total - s0, bucket)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt[s0:s0 + n]
        is_final = s0 + n >= total
        rem0 = req.max_new_tokens - 1
        eos0 = -1 if req.eos_id is None else int(req.eos_id)
        stats.add("serve/dispatch_launches")
        stats.add("serve/dispatches/prefill")
        flight.record(req.rid, "prefill-chunk", bucket=bucket,
                      start=int(s0), final=bool(is_final))
        with trace.span("serve/prefill", bucket=bucket, slot=slot,
                        rid=req.rid):
            (self.kc, self.vc, self.toks, self.lengths, self.last,
             self.active, self.remaining, self.eos_ids, self._rng,
             nxt) = self._prefill_fn(
                self._head, self._stacked, self.kc, self.vc, self.toks,
                self.lengths, self.last, self.active, self.remaining,
                self.eos_ids, jnp.int32(slot), jnp.asarray(padded),
                jnp.int32(s0), jnp.int32(total), jnp.asarray(is_final),
                jnp.int32(rem0), jnp.int32(eos0), self._rng)
        job["start"] = s0 + n
        if is_final:
            self._disp_rem[slot] = rem0
            self._pending.append(_Inflight("prefill", [(slot, req)], nxt,
                                           time.perf_counter()))
            trace.complete("serve/admit", job["t0"], slot=slot,
                           prompt=total, rid=req.rid)
        return bucket, is_final

    def _advance_admissions(self):
        """Dispatch up to ``_prefill_budget`` prompt tokens of waiting
        requests' prefill chunks (always at least one chunk when a job
        is open), pulling new requests into free slots as jobs
        finish."""
        if self._admitting:
            # a job whose request was deadline-evicted mid-admission is
            # abandoned (its slot is already free and may be re-used by
            # the next job; the partial prefill is inert — the slot
            # never activated and a successor overwrites it)
            self._admitting = collections.deque(
                j for j in self._admitting if not j["req"].done)
        budget = self._prefill_budget
        while budget > 0:
            if not self._admitting and not self._admit_next():
                return
            if not self._admitting:
                # handoff admission: rows installed directly, no
                # prefill job to chunk — pull the next waiter
                continue
            used, finished = self._dispatch_prefill_chunk(
                self._admitting[0])
            budget -= used
            if finished:
                self._admitting.popleft()

    # -- mid-decode handoff (ISSUE 16 drain migration) ----------------------

    def detach_handoff(self, req: Request):
        """Extract an in-flight request's KV rows + decode state and
        retire it locally WITHOUT finishing — the sending half of a
        drain migration on a slot-contiguous engine. The pipeline
        drains first, so rows ``[0, lengths)`` hold prompt +
        generated[:-1] and ``meta["tokens"]`` carries every token
        generated so far; the receiver re-emits the last one and
        continues bit-for-bit (fp32 wire).

        Returns ``(meta, k, v)`` with ``k``/``v`` presented as ONE
        wire page of ``n_tokens`` rows — (L, 1, Hkv, n_tokens, D) —
        so ``kv_transfer.encode_kv_pages`` and any ``submit_handoff``
        (dense or paged with matching geometry) accept them."""
        if req.failed:
            raise ValueError(f"request failed before detach: "
                             f"{req.error}")
        if not req.tokens:
            raise ValueError("no generated token yet — pump step() "
                             "until the request holds one")
        self._drain()
        if req.done:
            raise ValueError("request completed during drain — "
                             "publish its result directly")
        try:
            slot = self._slot_req.index(req)
        except ValueError:
            raise ValueError("request no longer holds a slot")
        # ptlint: disable=PT001 -- deliberate device→host sync: this IS
        # the migration payload leaving the draining replica
        n = int(self.lengths[slot])
        if n != len(req.prompt) + len(req.tokens) - 1:
            raise ValueError(
                f"slot {slot} length {n} inconsistent with prompt "
                f"{len(req.prompt)} + generated {len(req.tokens)} - 1")
        # ptlint: disable=PT001 -- same deliberate payload transfer
        rows_k = np.asarray(self.kc[:, slot, :, :n, :])
        rows_v = np.asarray(self.vc[:, slot, :, :n, :])
        k = rows_k[:, None]            # (L, 1, Hkv, n, D): one page
        v = rows_v[:, None]
        meta = {"prompt": list(req.prompt), "n_tokens": n,
                "first": int(req.tokens[0]),
                "tokens": [int(t) for t in req.tokens],
                "max_new_tokens": int(req.max_new_tokens),
                "eos_id": req.eos_id, "rid": req.rid}
        from paddle_tpu.observability import flight
        flight.record(req.rid, "handoff-detach", n_tokens=n,
                      generated=len(req.tokens))
        self._slot_req[slot] = None
        self.active = self.active.at[slot].set(False)
        self._disp_rem[slot] = 0
        req.done = True
        self._obs_request_end(req)
        return meta, k, v

    def submit_handoff(self, meta: dict, k, v,
                       deadline_s: Optional[float] = None) -> Request:
        """Receiving half of a migration: enqueue a request whose KV
        rows were built elsewhere. Accepts any page layout — (L, npg,
        Hkv, page, D) with ``npg*page >= n_tokens`` — so both dense
        (one page) and paged senders with matching (L, Hkv, D)
        geometry land here. Admission installs the rows and
        reconstructs the exact sender-side device state; the last
        sender-emitted token rides the harvest queue like a local
        prefill's first token."""
        import time
        prompt = [int(t) for t in meta["prompt"]]
        tokens = [int(t) for t in meta.get("tokens",
                                           [meta["first"]])]
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if not tokens:
            raise ValueError("handoff meta carries no tokens")
        max_new = int(meta["max_new_tokens"])
        if len(tokens) > max_new:
            raise ValueError("handoff carries more generated tokens "
                             "than its budget")
        n = int(meta.get("n_tokens", len(prompt) + len(tokens) - 1))
        if n != len(prompt) + len(tokens) - 1:
            raise ValueError(
                f"handoff meta inconsistent: n_tokens={n} != prompt "
                f"{len(prompt)} + generated {len(tokens)} - 1")
        if len(prompt) + max_new > self.T:
            raise ValueError(
                f"{len(prompt)} prompt + {max_new} new tokens exceed "
                f"cache length {self.T}")
        cfg = self.cfg
        k, v = np.asarray(k), np.asarray(v)
        for name, arr in (("k", k), ("v", v)):
            ok = (arr.ndim == 5 and arr.shape[0] == cfg.n_layers
                  and arr.shape[2] == cfg.kv_heads
                  and arr.shape[4] == cfg.head_dim
                  and arr.shape[1] * arr.shape[3] >= n)
            if not ok:
                raise ValueError(
                    f"handoff {name} pages shaped {tuple(arr.shape)} "
                    f"do not fit this engine's geometry (n_layers="
                    f"{cfg.n_layers}, kv_heads={cfg.kv_heads}, "
                    f"head_dim={cfg.head_dim}, rows>={n})")
        req = _HandoffRequest(
            prompt, max_new, meta["eos_id"],
            deadline=(None if deadline_s is None
                      else time.monotonic() + deadline_s),
            rid=meta.get("rid"))
        req.kv_tokens = tokens
        req.kv_ntok = n
        req.kv_wire = str(meta.get("wire", "lossy"))

        def rows(arr):
            L, npg, H, page, D = arr.shape
            return arr.transpose(0, 2, 1, 3, 4).reshape(
                L, H, npg * page, D)[:, :, :n, :]
        req.kv_rows = (rows(k), rows(v))
        self._waiting.append(req)
        return req

    def _admit_handoff(self, req: "_HandoffRequest", slot: int):
        """Install migrated rows instead of prefilling, then
        reconstruct the device state the sender's drained pipeline
        held: rows [0, n) live, ``tokens[-1]`` pending as ``last``
        (its KV is the next dispatch's write), token history row
        rebuilt so on-device drafts see the same window."""
        import time
        from paddle_tpu.observability import flight
        n = req.kv_ntok
        flight.record(req.rid, "handoff-install", n_tokens=n,
                      slot=slot, wire=req.kv_wire,
                      generated=len(req.kv_tokens))
        rows_k, rows_v = req.kv_rows
        self.kc = self.kc.at[:, slot, :, :n, :].set(
            jnp.asarray(rows_k, self.kc.dtype))
        self.vc = self.vc.at[:, slot, :, :n, :].set(
            jnp.asarray(rows_v, self.vc.dtype))
        req.kv_rows = None             # free the host copy
        seq = np.zeros((self.T,), np.int32)
        hist = req.prompt + req.kv_tokens      # n + 1 tokens
        seq[:len(hist)] = hist
        # ptlint: disable=PT001 -- seq is a host-built row; upload only
        self.toks = self.toks.at[slot].set(jnp.asarray(seq))
        req.tokens = list(req.kv_tokens[:-1])
        nxt = req.kv_tokens[-1]
        rem0 = req.max_new_tokens - len(req.kv_tokens)
        eos0 = -1 if req.eos_id is None else int(req.eos_id)
        alive = rem0 > 0 and (eos0 < 0 or nxt != eos0)
        self.lengths = self.lengths.at[slot].set(n)
        self.last = self.last.at[slot].set(jnp.int32(nxt))
        self.active = self.active.at[slot].set(bool(alive))
        self.remaining = self.remaining.at[slot].set(rem0)
        self.eos_ids = self.eos_ids.at[slot].set(eos0)
        self._slot_req[slot] = req
        self._disp_rem[slot] = rem0
        self._pending.append(_Inflight("prefill", [(slot, req)],
                                       np.int32(nxt),
                                       time.perf_counter()))

    def _emit(self, slot: int, req: Request, token: int):
        req.tokens.append(token)
        self._obs_first_token(req)
        if self.on_token is not None:
            self.on_token(req, token)
        hit_eos = req.eos_id is not None and token == req.eos_id
        if hit_eos or len(req.tokens) >= req.max_new_tokens:
            req.done = True
            self._slot_req[slot] = None
            self.active = self.active.at[slot].set(False)
            self._obs_request_end(req)

    def step(self) -> int:
        """Advance the serving pipeline: evict expired requests (a hard
        drain boundary), dispatch waiting prefill chunks and one decode
        dispatch, then harvest the OLDEST in-flight dispatch once the
        pipeline holds ``depth`` of them — lag-one at the default depth
        2, fully synchronous at depth 1. Returns tokens applied to
        Requests this call; at depth>1 they come from an earlier
        dispatch, so drain with run() (or ``drain()``) before reading
        final Request state."""
        import time
        from paddle_tpu.observability import trace
        t0 = time.perf_counter()
        base = self.tokens_emitted
        with trace.span("serve/step") as sp:
            self._evict_expired()
            self._advance_admissions()
            self._pump(self._dispatch_decode())
            live = self.num_active
            n = self.tokens_emitted - base
            sp.attrs["active"] = live
            sp.attrs["tokens"] = n
            sp.attrs["waiting"] = len(self._waiting)
        if live or n:
            self._obs_step(t0, n, live)
        return n

    def _dispatch_decode(self) -> bool:
        """Enqueue ONE decode dispatch over every live slot (chunked or
        speculative; the plain path is the chunk=1 instance). Pure
        enqueue — nothing is pulled back to host here; the packed
        results join the harvest queue."""
        from paddle_tpu.observability import trace
        live = [(s, r) for s, r in enumerate(self._slot_req)
                if r is not None and self._disp_rem[s] > 0]
        if not live:
            return False
        self.steps += 1
        self._obs_host_gap()
        if self.spec_k:
            with trace.span("serve/dispatch", kind="spec", k=self.spec_k,
                            chunk=self.chunk,
                            inflight=len(self._pending)):
                (self.kc, self.vc, self.toks, self.lengths, self.last,
                 self.active, self.remaining, packed) = self._verify_fn(
                    self._head, self._stacked, self.kc, self.vc,
                    self.toks, self.lengths, self.last, self.active,
                    self.remaining, self.eos_ids, self._poison_mask())
            kind = "spec"
        else:
            with trace.span("serve/dispatch", kind="chunk",
                            chunk=self.chunk,
                            inflight=len(self._pending)):
                (self.kc, self.vc, self.lengths, self.last, self.active,
                 self.remaining, self._rng, packed) = self._multi_fn(
                    self._head, self._stacked, self.kc, self.vc,
                    self.lengths, self.last, self.active, self.remaining,
                    self.eos_ids, self._rng, self._poison_mask())
            kind = "decode"
        self._finish_dispatch(kind, live, packed)
        return True

    def _retire_done(self, live):
        """Free slots whose request hit its budget or eos (mirrors the
        device-side early-stop) — shared by both harvest paths. Guards
        against stale snapshots: a slot already freed and re-admitted
        must not be clobbered by an older dispatch's record."""
        for slot, req in live:
            if req.done or self._slot_req[slot] is not req:
                continue
            if len(req.tokens) >= req.max_new_tokens or (
                    req.eos_id is not None and req.tokens
                    and req.tokens[-1] == req.eos_id):
                req.done = True
                self._slot_req[slot] = None
                self._disp_rem[slot] = 0
                self._obs_request_end(req)

    def _apply_token(self, slot: int, req: Request, token: int):
        # the FIRST generated token always rides a 'prefill' record
        # (_emit), so TTFT needs no check here — only the stream hook
        req.tokens.append(token)
        if self.on_token is not None:
            self.on_token(req, token)

    def _after_replay(self, rec):
        self._retire_done(rec.live)

    def warmup(self):
        """Pre-trace and compile every jitted function this engine can
        dispatch — one prefill per bucket plus the decode path — on
        throwaway state mirrors, so the first requests pay no compile
        latency. The KV caches transiently exist twice while warming
        (the mirrors are donated through the chain and freed at the
        end); with a persistent compilation cache the compiles
        themselves are amortized across processes."""
        import time
        from paddle_tpu import stats
        t0 = time.perf_counter()
        kc, vc = jnp.zeros_like(self.kc), jnp.zeros_like(self.vc)
        toks = jnp.zeros_like(self.toks)
        lengths = jnp.zeros_like(self.lengths)
        last = jnp.zeros_like(self.last)
        active = jnp.zeros_like(self.active)
        remaining = jnp.zeros_like(self.remaining)
        eos_ids = jnp.zeros_like(self.eos_ids)
        rng = jax.random.PRNGKey(0)
        for b in self.buckets:
            (kc, vc, toks, lengths, last, active, remaining, eos_ids,
             rng, _) = self._prefill_fn(
                self._head, self._stacked, kc, vc, toks, lengths, last,
                active, remaining, eos_ids, jnp.int32(0),
                jnp.zeros((1, b), jnp.int32), jnp.int32(0), jnp.int32(1),
                jnp.asarray(False), jnp.int32(0), jnp.int32(-1), rng)
        poison = jnp.zeros((self.S,), bool)
        if self.spec_k:
            out = self._verify_fn(self._head, self._stacked, kc, vc,
                                  toks, lengths, last, active, remaining,
                                  eos_ids, poison)
        else:
            out = self._multi_fn(self._head, self._stacked, kc, vc,
                                 lengths, last, active, remaining,
                                 eos_ids, rng, poison)
        jax.block_until_ready(out)
        stats.observe("serve/warmup_s", time.perf_counter() - t0)

    def run(self) -> None:
        """Drain: run steps until every submitted request is done, then
        harvest any trailing no-op dispatches (all requests can retire
        while younger dispatches are still in flight — their flags are
        all False, but their device buffers must not outlive the
        work)."""
        while self._waiting or any(r is not None for r in self._slot_req):
            self.step()
        self._drain()

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    def dispatch_cost(self, name=None):
        """ISSUE 15 roofline capture: AOT cost/memory analysis of ONE
        decode dispatch at the CURRENT geometry — XLA's FLOPs and HBM
        bytes for the exact program the serving loop launches (the
        spec-verify program when ``speculative_k`` is set). Lowers
        without executing, so donated buffers stay live; compilation
        rides the jit cache on a warmed engine. Records ``prof/flops``
        / ``prof/hbm_bytes`` / ``mem/compiled_*`` under ``name``
        (default: the path name)."""
        from paddle_tpu.observability import devprof
        if self.spec_k:
            return devprof.capture_jit(
                self._verify_fn, self._head, self._stacked, self.kc,
                self.vc, self.toks, self.lengths, self.last,
                self.active, self.remaining, self.eos_ids,
                self._poison_mask(), name=name or "spec")
        return devprof.capture_jit(
            self._multi_fn, self._head, self._stacked, self.kc,
            self.vc, self.lengths, self.last, self.active,
            self.remaining, self.eos_ids, self._rng,
            self._poison_mask(), name=name or "decode")

    def dispatch_fn_args(self):
        """The jitted decode dispatch and the exact argument tuple the
        serving loop calls it with (the spec-verify program when
        ``speculative_k`` is set) — for launch accounting
        (``devprof.count_pallas_launches`` /
        ``count_hlo_custom_calls``) without executing anything."""
        if self.spec_k:
            return (self._verify_fn,
                    (self._head, self._stacked, self.kc, self.vc,
                     self.toks, self.lengths, self.last, self.active,
                     self.remaining, self.eos_ids, self._poison_mask()))
        return (self._multi_fn,
                (self._head, self._stacked, self.kc, self.vc,
                 self.lengths, self.last, self.active, self.remaining,
                 self.eos_ids, self._rng, self._poison_mask()))


def decode_roofline_tokens_per_sec(cfg, batch: int, context: int,
                                   hbm_gbps: float,
                                   weight_bytes: int = 2,
                                   cache_bytes: int = 2) -> float:
    """HBM-bandwidth upper bound on decode throughput.

    Per decode step the chip must read every weight once (batch-amortized)
    plus each sequence's KV prefix: steps/s = BW / (W + B * kv_bytes),
    tok/s = B * steps/s. This is the number BENCH compares achieved decode
    against (VERDICT r4: r02 decode sat at ~43% of this bound).
    """
    n = cfg.num_params()
    kv_heads = getattr(cfg, "kv_heads", cfg.n_heads)  # GQA shrinks this
    kv = 2 * cfg.n_layers * kv_heads * cfg.head_dim * context
    step_bytes = n * weight_bytes + batch * kv * cache_bytes
    return batch * hbm_gbps * 1e9 / step_bytes
