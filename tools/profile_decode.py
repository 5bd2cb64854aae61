"""Decode-engine throughput probe for real hardware.

Times the full continuous-batching engine loop against the HBM roofline
across (slots, cache length, chunk) points — the knobs that matter for
serving. PD_SIZE=350m for a smaller model; PD_SPEC=1 adds a chunked
speculative run on repetitive prompts; PD_SECTIONS=engine,paged,prof
picks report sections; PD_PREFIX=1 adds the repeated-system-prompt
sweep (cold vs warm radix-cache admission, asserted — the `tools/ci.sh
paged` smoke gate); PD_SECTIONS=prof runs the ISSUE 15 device-time
attribution sweep (roofline fraction, launch tax, step decomposition
per decode path across PD_LENGTHS prompt lengths — the `tools/ci.sh
prof` gate).

Measurement notes:
- ``jax.block_until_ready`` blocks; the engine's own host loop syncs
  naturally when it harvests a dispatch's packed result.
- Only in-jit loops (the engine's ``steps_per_call`` chunking) measure
  device time free of host dispatch. For sub-step breakdowns, time a
  lax.scan of K steps at two K values and use the slope.
- Measure the run-to-run spread before trusting an A/B difference.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from paddle_tpu.models import gpt
from paddle_tpu.inference.decode_engine import (
    DecodeEngine, decode_roofline_tokens_per_sec)


def release_engine(eng):
    """Drop an engine's big device buffers — the donor weight stack and
    whichever KV pool attributes the engine variant carries — so the next
    engine built in this process doesn't OOM against the last one's
    arrays. The ONE definition (was copy-pasted at three sites): tolerant
    of attrs a variant lacks and of the sharded stacked state (a pytree
    of per-device arrays nulls the same way a single-chip stack does)."""
    for attr in ("kc", "vc", "kp", "vp", "_stacked"):
        if hasattr(eng, attr):
            setattr(eng, attr, None)


def pipeline_report(eng):
    """ISSUE 4: in-flight depth, per-step host gap, and dispatch/harvest
    overlap, measured from the trace ring + stats histograms of the run
    just finished. 'overlap' = fraction of harvests that blocked while
    at least one younger dispatch was already enqueued (the lag-one
    win); 'host_gap' = host-side bubble between consecutive dispatch
    enqueues — what the device idles on at depth 1."""
    from paddle_tpu import stats
    from paddle_tpu.observability import trace
    snap = stats.snapshot("serve/")
    evs, _ = trace.events()
    spans = [e for e in evs if e is not None]
    disp = [e for e in spans if e[0] == "serve/dispatch"]
    harv = [e for e in spans if e[0] == "serve/harvest"]
    # overlap over DECODE harvests only (prefill records are admission
    # plumbing): the fraction whose blocking readback ran while a
    # younger dispatch was already keeping the device busy
    dec = [e for e in harv if (e[6] or {}).get("kind") != "prefill"]
    overlapped = sum(1 for e in dec
                     if (e[6] or {}).get("inflight", 0) >= 1)
    return {
        "depth": eng.depth,
        "host_gap_p50_ms": snap.get("serve/host_gap_s.p50", 0) * 1e3,
        "host_gap_p99_ms": snap.get("serve/host_gap_s.p99", 0) * 1e3,
        "dispatch_ms": sum(e[2] for e in disp) / 1e6,
        "harvest_ms": sum(e[2] for e in harv) / 1e6,
        "overlap": overlapped / max(1, len(dec)),
    }


def run_engine(model, slots=8, s_pf=128, n_new=128, chunk=64, spec_k=0,
               inflight=None, warmup=False):
    from paddle_tpu import stats
    from paddle_tpu.observability import trace
    cfg = model.cfg
    eng = DecodeEngine(model, max_slots=slots,
                       max_len=s_pf + n_new + (128 + spec_k if spec_k
                                               else 0),
                       steps_per_call=chunk, speculative_k=spec_k,
                       inflight=inflight, warmup=warmup)
    rs = np.random.RandomState(1)
    if spec_k:   # repetition-heavy prompts: the regime spec serves
        loops = [list(rs.randint(0, cfg.vocab_size, 8))
                 for _ in range(slots)]
        prompts = [(lp * (s_pf // 8 + 1))[:s_pf] for lp in loops]
    else:
        prompts = [rs.randint(0, cfg.vocab_size, s_pf)
                   for _ in range(slots)]
    for p in prompts:
        eng.submit(p, max_new_tokens=2)
    eng.run()  # warm compile (no-op with warmup=True)
    stats.reset("serve/")
    trace.clear(capacity=65536)
    trace.enable()          # in-memory ring only: no file unless asked
    reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    eng.step()
    pre = sum(len(r.tokens) for r in reqs)
    d0 = eng.steps
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs) - pre
    dispatches = eng.steps - d0
    rep = pipeline_report(eng)
    trace.disable()
    trace.clear()
    release_engine(eng)
    del eng
    return toks / dt, dispatches, rep


def run_paged(model, prompts, n_new=128, chunk=64, inflight=None,
              n_pages=None, max_slots=None):
    """Paged-engine drain timing (ISSUE 6): submit `prompts`, time the
    drain, and return (tok/s, dispatches, pipeline report, prefix
    stats). The engine keeps the prefix radix cache at its default
    (on), so repeated calls against the same engine measure warm-cache
    admission; pass fresh random prompts for a cold decode number."""
    from paddle_tpu import stats
    from paddle_tpu.observability import trace
    from paddle_tpu.inference.paged_engine import PagedDecodeEngine
    page = 128
    slots = max_slots or len(prompts)
    if n_pages is None:
        need = max(len(p) + n_new for p in prompts)
        n_pages = slots * ((need + page - 1) // page + 1) + 4
    eng = PagedDecodeEngine(model, n_pages=n_pages, max_slots=slots,
                            page_size=page, steps_per_call=chunk,
                            inflight=inflight)
    # warm the compiles on DISJOINT prompts of the same lengths so the
    # timed round's trie lookups miss (its tok/s stays a decode number)
    rs = np.random.RandomState(4242)
    vocab = eng.cfg.vocab_size
    for p in prompts:
        eng.submit(list(rs.randint(0, vocab, len(p))), max_new_tokens=2)
    eng.run()
    stats.reset("serve/")
    trace.clear(capacity=65536)
    trace.enable()
    reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    eng.step()
    pre = sum(len(r.tokens) for r in reqs)
    d0 = eng.steps
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs) - pre
    dispatches = eng.steps - d0
    rep = pipeline_report(eng)
    snap = stats.snapshot("serve/")
    n_prompt = sum(len(p) for p in prompts)
    pfx = {
        "hit_tokens": int(snap.get("serve/prefix_hit_tokens", 0)),
        "lookups": int(snap.get("serve/prefix_lookup", 0)),
        "hit_rate": snap.get("serve/prefix_hit_tokens", 0)
        / max(1, n_prompt),
        "pool_free": int(snap.get("serve/pool_pages_free", 0)),
        "pool_shared": int(snap.get("serve/pool_pages_shared", 0)),
    }
    trace.disable()
    trace.clear()
    release_engine(eng)
    del eng
    return toks / dt, dispatches, rep, pfx


def prefix_sweep(model, slots, shared_len, tail_len, n_new, chunk):
    """PD_PREFIX=1: repeated-system-prompt sweep. Round 1 submits
    `slots` prompts sharing one page-aligned `shared_len`-token system
    prefix (cold: registers the chain); round 2 submits NEW tails
    behind the same prefix (warm: must prefill only the tails). Prints
    admission+drain wall time and hit tokens for both rounds and
    asserts the warm round actually hit — `tools/ci.sh paged` relies
    on that assert as its regression gate."""
    from paddle_tpu import stats
    from paddle_tpu.inference.paged_engine import PagedDecodeEngine
    cfg = model.cfg
    page = 128
    assert shared_len % page == 0, "system prefix must be page-aligned"
    rs = np.random.RandomState(7)
    shared = list(rs.randint(0, cfg.vocab_size, shared_len))
    need = shared_len + tail_len + n_new
    n_pages = 2 * (shared_len // page) + slots * (
        (need + page - 1) // page + 1) + 4
    eng = PagedDecodeEngine(model, n_pages=n_pages, max_slots=slots,
                            page_size=page, steps_per_call=chunk)
    # compile warm-up on a TRIE-DISJOINT prefix at the exact timed
    # geometry: first submit traces the full prefill (the cold round's
    # shape), the second — same warm prefix, new tail — traces the
    # suffix prefill (the warm round's shape). The timed rounds then
    # measure prefill/decode work, not jit compilation.
    warm_pfx = list(rs.randint(0, cfg.vocab_size, shared_len))
    for _ in range(2):
        eng.submit(warm_pfx + list(rs.randint(0, cfg.vocab_size,
                                              tail_len)),
                   max_new_tokens=n_new)
        eng.run()

    def round_(label):
        stats.reset("serve/prefix")
        prompts = [shared + list(rs.randint(0, cfg.vocab_size, tail_len))
                   for _ in range(slots)]
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        eng.run()
        dt = time.perf_counter() - t0
        snap = stats.snapshot("serve/prefix")
        hits = int(snap.get("serve/prefix_hit_tokens", 0))
        toks = sum(len(r.tokens) for r in reqs)
        print(f"  {label}: {dt * 1e3:.1f}ms wall "
              f"({toks} new tokens, {slots}x({shared_len}+{tail_len}) "
              f"prompt) prefix_hit_tokens={hits}", flush=True)
        return hits

    print(f"prefix sweep: shared system prompt {shared_len} tokens, "
          f"{slots} slots", flush=True)
    cold = round_("cold")
    warm = round_("warm")
    # the warm round must hit at least one full shared page per slot —
    # the submit path then prefills only the suffix tokens
    assert warm >= slots * page, (
        f"warm shared-prefix round hit only {warm} tokens "
        f"(expected >= {slots * page}): prefix cache regressed")
    assert warm > cold, "warm round should out-hit the cold round"
    release_engine(eng)
    del eng


def _prof_run(eng, prompts, n_new):
    """One timed drain for the prof section: warm on trie-disjoint
    prompts of the same lengths, then measure tokens / wall /
    dispatch-launch count / step decomposition over the timed window
    (stats + trace ring reset at its start)."""
    from paddle_tpu import stats
    from paddle_tpu.observability import devprof, trace
    rs = np.random.RandomState(99)
    for p in prompts:
        eng.submit(list(rs.randint(0, eng.cfg.vocab_size, len(p))),
                   max_new_tokens=2)
    eng.run()
    stats.reset("serve/")
    trace.clear(capacity=65536)
    trace.enable()
    reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs)
    launches = int(stats.get("serve/dispatch_launches", 0))
    frac = devprof.step_fractions()
    trace.disable()
    trace.clear()
    return toks, wall, launches, frac


def prof_section(model, size):
    """ISSUE 15 tentpole report: device-time attribution per decode
    path (contiguous + paged) across a prompt-length sweep. Each row
    prints measured tok/s vs the AOT cost-analysis roofline tok/s, the
    roofline fraction, dispatch launches per token, and the launch-tax
    fraction of token time — the 'one-pallas-launch-per-layer at short
    lengths' hypothesis as a number. PD_LENGTHS overrides the sweep
    (>=3 lengths keep the tax-vs-length curve readable). The asserts
    are the `tools/ci.sh prof` smoke gate."""
    from paddle_tpu.observability import devprof
    from paddle_tpu.inference.paged_engine import PagedDecodeEngine
    cfg = model.cfg
    tiny = size == "tiny"
    default = "32,64,128" if tiny else "128,512,1024"
    lengths = [int(x) for x in os.environ.get(
        "PD_LENGTHS", default).split(",") if x.strip()]
    slots, n_new = (4, 16) if tiny else (8, 64)
    chunk = 4 if tiny else 32
    page = 128
    tax = devprof.launch_tax_s()
    ptax = devprof.pallas_launch_tax_s()
    line = f"launch tax: jit no-op {tax * 1e6:.0f}us/dispatch"
    if ptax is not None:
        line += (f", pallas no-op {ptax * 1e6:.0f}us/launch "
                 f"(x{2 * cfg.n_layers} launches/dispatch on the "
                 f"fused paged path)")
    print(line, flush=True)
    rs = np.random.RandomState(13)
    donor = None
    for path in ("contiguous", "paged"):
        for s_pf in lengths:
            if path == "contiguous":
                eng = DecodeEngine(
                    model if donor is None else None, max_slots=slots,
                    max_len=s_pf + n_new, steps_per_call=chunk,
                    share_weights_with=donor)
                if donor is None:
                    donor = eng
            else:
                n_pages = slots * ((s_pf + n_new + page - 1) // page
                                   + 1) + 4
                eng = PagedDecodeEngine(
                    None, n_pages=n_pages, max_slots=slots,
                    page_size=page, steps_per_call=chunk,
                    share_weights_with=donor)
            prompts = [list(rs.randint(0, cfg.vocab_size, s_pf))
                       for _ in range(slots)]
            toks, wall, launches, frac = _prof_run(eng, prompts, n_new)
            name = f"{path}_{s_pf}"
            cap = eng.dispatch_cost(name=name)
            aroof = devprof.roofline_tokens_per_sec(
                cap, toks / max(1, launches))
            rfrac = devprof.record_roofline(name, toks / wall, aroof)
            lt = devprof.launch_tax_fraction(launches, wall, name=name)
            print(f"prof {path} len={s_pf}: {toks / wall:.1f} tok/s "
                  f"vs roofline {aroof:.1f} (frac {rfrac:.3f}) "
                  f"launches/token={launches / max(1, toks):.3f} "
                  f"launch_tax_frac={lt:.3f} "
                  f"flops/dispatch={cap.flops:.3g} "
                  f"hbm_bytes/dispatch={cap.hbm_bytes:.3g}",
                  flush=True)
            if frac:
                print(f"  step split: device={frac['device_frac']:.0%} "
                      f"queue={frac['queue_frac']:.0%} "
                      f"host={frac['host_frac']:.0%}"
                      + ("  [HOST-BOUND]" if frac["host_bound"]
                         else ""), flush=True)
            # `tools/ci.sh prof` gate: the capture must be real and the
            # tax fraction a sane fraction of the wall
            assert cap.flops > 0 and cap.hbm_bytes > 0, (
                f"{name}: cost_analysis returned no flops/bytes")
            assert 0 < lt <= 1.0, f"{name}: launch_tax_frac {lt}"
            assert launches > 0 and toks > 0
            if eng is not donor:
                release_engine(eng)
            del eng
    release_engine(donor)


def main():
    size = os.environ.get("PD_SIZE", "1p3b")
    cfg = (gpt.gpt3_1p3b(max_seq_len=2048) if size == "1p3b"
           else gpt.gpt_tiny(max_seq_len=512) if size == "tiny"
           else gpt.gpt3_350m(max_seq_len=1024))
    print("building model", size, flush=True)
    model = gpt.GPT(cfg, seed=0)
    dev = jax.devices()[0]
    print("device:", dev, flush=True)

    from paddle_tpu.cost_model import _peak
    hbm = _peak(dev)[1] / 1e9

    def show(label, tps, disp, roof, rep):
        print(f"{label}: {tps:.1f} tok/s ({disp} dispatches) "
              f"roofline={roof:.0f} ratio={tps / roof:.3f}", flush=True)
        print(f"  pipeline: depth={rep['depth']} "
              f"host_gap p50={rep['host_gap_p50_ms']:.2f}ms "
              f"p99={rep['host_gap_p99_ms']:.2f}ms "
              f"dispatch={rep['dispatch_ms']:.1f}ms "
              f"harvest={rep['harvest_ms']:.1f}ms "
              f"overlap={rep['overlap']:.0%}", flush=True)

    # PD_INFLIGHT sweeps explicit depths (e.g. PD_INFLIGHT=1,2,4) to
    # A/B the pipeline against the synchronous baseline; unset uses the
    # engine default (PT_SERVE_INFLIGHT or 2). PD_SECTIONS picks which
    # report sections run ("engine,paged" default; `tools/ci.sh paged`
    # runs sections=paged on the tiny model as its CPU smoke).
    sweep = [int(x) for x in os.environ.get("PD_INFLIGHT", "").split(",")
             if x.strip()] or [None]
    sections = {s.strip() for s in os.environ.get(
        "PD_SECTIONS", "engine,paged").split(",") if s.strip()}

    if "engine" in sections:
        for slots, s_pf, n_new in ((8, 128, 128), (16, 128, 128)):
            roof = decode_roofline_tokens_per_sec(
                cfg, slots, s_pf + n_new // 2, hbm)
            for depth in sweep:
                tps, disp, rep = run_engine(model, slots=slots,
                                            s_pf=s_pf, n_new=n_new,
                                            inflight=depth)
                show(f"slots={slots} ctx={s_pf}+{n_new}", tps, disp,
                     roof, rep)

    if os.environ.get("PD_SPEC", "0") == "1" and "engine" in sections:
        roof = decode_roofline_tokens_per_sec(cfg, 8, 192, hbm)
        for depth in sweep:
            tps, disp, rep = run_engine(model, chunk=16, spec_k=4,
                                        inflight=depth)
            show("spec k=4 chunk=16", tps, disp, roof, rep)

    if "paged" in sections:
        # paged decode vs the SAME analytic HBM roofline the contiguous
        # engine is scored against (decode is bandwidth-bound; paging
        # changes layout, not bytes-that-must-move) — the gap between
        # the two ratios is the paged kernel's overhead. Fresh random
        # prompts per depth keep the timed round prefix-cold so the
        # tok/s is a decode number, not an admission number.
        tiny = size == "tiny"
        slots, s_pf, n_new = (4, 128, 16) if tiny else (8, 128, 128)
        chunk = 8 if tiny else 64
        roof = decode_roofline_tokens_per_sec(
            cfg, slots, s_pf + n_new // 2, hbm)
        rs = np.random.RandomState(11)
        for depth in sweep:
            prompts = [list(rs.randint(0, cfg.vocab_size, s_pf))
                       for _ in range(slots)]
            tps, disp, rep, pfx = run_paged(model, prompts, n_new=n_new,
                                            chunk=chunk, inflight=depth)
            show(f"paged slots={slots} ctx={s_pf}+{n_new}", tps, disp,
                 roof, rep)
            print(f"  prefix: hit_rate={pfx['hit_rate']:.0%} "
                  f"hit_tokens={pfx['hit_tokens']} "
                  f"lookups={pfx['lookups']} "
                  f"pool free={pfx['pool_free']} "
                  f"shared={pfx['pool_shared']}", flush=True)

        if os.environ.get("PD_PREFIX", "0") == "1":
            prefix_sweep(model, slots=slots,
                         shared_len=256 if not tiny else 128,
                         tail_len=32, n_new=8 if tiny else 32,
                         chunk=chunk)

    if "prof" in sections:
        prof_section(model, size)


if __name__ == "__main__":
    main()
