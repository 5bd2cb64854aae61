"""Overlap-aware collectives (ISSUE 11): bucketed in-backward gradient
sync, one-layer-ahead weight prefetch, ICI+DCN striping, and the
comm-exposed-time accounting.

The load-bearing assertions:

- jaxpr interleaving: with overlap ON the backward scan body contains
  one quantized reduce-scatter per bucket (≥2 buckets on the test
  model) AND the stage-3 gather, instead of a single fused tail
  collective — and the forward scan carries the gathered weights
  (double-buffered prefetch);
- parity: the overlap step's loss trajectory matches the PR 7 quantized
  step within PR 7's established tolerances, and toggling overlap alone
  (prefetch pinned) is BIT-identical;
- exposed-time algebra is exact on synthetic interval sets (nested,
  overlapping, back-to-back).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu.distributed as dist
from paddle_tpu import flags as pt_flags
from paddle_tpu import optimizer as optim
from paddle_tpu import stats
from paddle_tpu.distributed import compression as C
from paddle_tpu.distributed import overlap as OV
from paddle_tpu.distributed import planner
from paddle_tpu.distributed.sharding import (
    attach_comm_ef, build_group_sharded_step, init_group_sharded_state)
from paddle_tpu.observability import comm as obs_comm


@pytest.fixture
def fsdp_mesh():
    topo = dist.init_mesh(fsdp=4, devices=jax.devices()[:4],
                          set_global=False)
    yield topo
    from paddle_tpu.distributed import mesh as mesh_lib
    mesh_lib.set_topology(None)


def _batch(seed=0, b=16, d=16, k=8):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(b, d), jnp.float32),
            jnp.asarray(rs.randn(b, k), jnp.float32))


def _run(mesh, steps=5, **kw):
    params, stacked, emb, blk, lf = OV.mlp_block_model(n_layers=3)
    x, y = _batch()
    kw.setdefault("bucket_mb", 1e-4)   # tiny budget → one bucket per leaf
    sp, st, step = OV.overlap_parallel(
        dict(params), emb, blk, lf, optim.SGD(learning_rate=0.05),
        mesh, stacked, **kw)
    losses = []
    for _ in range(steps):
        sp, st, loss = step(sp, st, x, y)
        losses.append(float(loss))
    return sp, st, losses


# -- bucket partitioning (engine-free) ---------------------------------------

def test_partition_buckets_reverse_layer_order():
    leaves = [("l0", 100), ("l1", 100), ("l2", 100)]
    buckets = OV.partition_buckets(leaves, bucket_mb=1e-5)  # ~10 bytes
    assert buckets == [["l2"], ["l1"], ["l0"]]
    # forward order opt-out
    assert OV.partition_buckets(leaves, bucket_mb=1e-5, reverse=False) \
        == [["l0"], ["l1"], ["l2"]]


def test_partition_buckets_mb_budget_accumulates_tiny_leaves():
    mb = 2.0 ** 20
    leaves = [("a", mb // 4), ("b", mb // 4), ("c", mb // 4),
              ("d", mb // 4), ("e", mb // 4)]
    buckets = OV.partition_buckets(leaves, bucket_mb=1.0)
    # reverse order, four quarter-MB leaves fill the 1MB budget
    assert buckets == [["e", "d", "c", "b"], ["a"]]


def test_partition_buckets_oversized_leaf_clamps_to_own_bucket():
    """A leaf bigger than the whole budget forms its own bucket rather
    than splitting — the bucket clamps to the leaf (PR 7's tiny-leaf
    block clamp, in the other direction)."""
    mb = 2.0 ** 20
    leaves = [("small", 64), ("huge", 8 * mb), ("tail", 64)]
    buckets = OV.partition_buckets(leaves, bucket_mb=1.0)
    assert buckets == [["tail"], ["huge"], ["small"]]


def test_partition_buckets_single_bucket_under_budget():
    leaves = [("a", 10), ("b", 10)]
    assert OV.partition_buckets(leaves, bucket_mb=64) == [["b", "a"]]


# -- exposed-time accounting --------------------------------------------------

def test_exposed_time_exact_uncovered_measure():
    # comm [0,4], compute [1,2] and [3,3.5] → exposed 1 + 1 + 0.5
    assert obs_comm.exposed_time([(0, 4)], [(1, 2), (3, 3.5)]) \
        == pytest.approx(1.0 + 1.0 + 0.5)
    # fully covered → 0
    assert obs_comm.exposed_time([(1, 2)], [(0, 4)]) == pytest.approx(0.0)
    # no compute at all → everything exposed
    assert obs_comm.exposed_time([(0, 1), (2, 3)], []) == pytest.approx(2.0)


def test_exposed_time_nested_and_back_to_back_spans():
    # nested comm spans must union, not double-count: [0,4] contains [1,2]
    comm = [(0, 4), (1, 2)]
    assert obs_comm.exposed_time(comm, []) == pytest.approx(4.0)
    # back-to-back compute [0,1][1,2] covers comm [0.5,1.5] completely
    assert obs_comm.exposed_time([(0.5, 1.5)], [(0, 1), (1, 2)]) \
        == pytest.approx(0.0)
    # nested compute spans (parent [0,10], child [2,3]) cover once
    assert obs_comm.exposed_time([(1, 4)], [(0, 10), (2, 3)]) \
        == pytest.approx(0.0)
    # overlapping comm spans against partial compute
    assert obs_comm.exposed_time([(0, 2), (1, 3)], [(0, 1)]) \
        == pytest.approx(2.0)


def test_overlap_fraction_and_step_overlap_from_events():
    # synthetic trace events: (name, t0_ns, dur_ns, tid, sid, parent, attrs)
    ev = [
        ("compute/step", int(0e9), int(2e9), 1, 1, 0, None),
        ("collective/all_to_all", int(1e9), int(2e9), 1, 2, 1, None),
        ("collective/all_gather", int(3e9), int(1e9), 1, 3, 1, None),
        ("serve/other", int(0e9), int(9e9), 1, 4, 0, None),
    ]
    e, frac, busy = obs_comm.step_overlap(events=ev)
    # comm busy [1,3]∪[3,4] = 3s; compute [0,2] covers [1,2] → exposed 2
    assert busy == pytest.approx(3.0)
    assert e == pytest.approx(2.0)
    assert frac == pytest.approx(1.0 - 2.0 / 3.0)
    # no comm → fraction 1.0 (nothing exposed)
    e0, f0, b0 = obs_comm.step_overlap(events=[ev[0]])
    assert (e0, f0, b0) == (0.0, 1.0, 0.0)


def test_record_step_overlap_ticks_stats():
    stats.reset("comm/")
    ev = [("compute/step", 0, int(1e9), 1, 1, 0, None),
          ("collective/psum", 0, int(2e9), 1, 2, 0, None)]
    e, frac, busy = obs_comm.record_step_overlap(events=ev)
    assert e == pytest.approx(1.0)
    assert stats.get("comm/overlap_frac") == pytest.approx(0.5)
    snap = stats.snapshot()
    assert any("comm/exposed_s" in k for k in snap), snap.keys()


# -- the bucket codec ---------------------------------------------------------

def test_bucket_rs_matches_per_leaf_rs(fsdp_mesh):
    """One concatenated bucket exchange computes the same mean (within
    block-scaling tolerance of the per-leaf codec — block boundaries
    shift inside the concatenation) and the same error-feedback algebra:
    v = mean + … with ef = v − own-dequant."""
    rs = np.random.RandomState(1)
    g1 = jnp.asarray(rs.randn(16, 8), jnp.float32)
    g2 = jnp.asarray(rs.randn(32,), jnp.float32)

    def body(a, b):
        sh, ef, ok = C.quantized_bucket_reduce_scatter(
            {"a": a, "b": b}, {"a": jnp.zeros_like(a),
                               "b": jnp.zeros_like(b)},
            "fsdp", "int8", block=64, dims={"a": 0, "b": 0})
        return sh["a"], sh["b"], ef["a"], ef["b"], ok

    sm = shard_map(body, mesh=fsdp_mesh.mesh, in_specs=(P(), P()),
                   out_specs=(P("fsdp"), P("fsdp"), P(), P(), P()),
                   check_vma=False)
    sa, sb, ea, eb, ok = jax.jit(sm)(g1, g2)
    assert bool(ok)
    # every rank fed the same g → the "mean" is g itself ± quant error.
    # Inside a bucket, quantization blocks span leaf boundaries, so the
    # half-step bound uses the BUCKET's amax, not each leaf's own.
    bound = max(float(jnp.max(jnp.abs(g1))),
                float(jnp.max(jnp.abs(g2)))) * (0.5 / 127) + 1e-6
    assert float(jnp.max(jnp.abs(sa - g1))) <= bound
    assert float(jnp.max(jnp.abs(sb - g2))) <= bound
    # ef = v − own-dequant, bounded by the same half-step
    assert float(jnp.max(jnp.abs(ea))) <= bound
    assert float(jnp.max(jnp.abs(eb))) <= bound
    assert float(jnp.max(jnp.abs(ea))) > 0.0


def test_bucket_rs_fp32_exact_zero_ef(fsdp_mesh):
    """method=None: the bucket exchange is exact and the residual is
    identically zero — the scheduling A/B baseline changes no math."""
    rs = np.random.RandomState(2)
    g = jnp.asarray(rs.randn(4, 16, 8), jnp.float32)  # per-rank rows

    def body(gl):
        sh, ef, ok = C.quantized_bucket_reduce_scatter(
            {"w": gl[0]}, {"w": jnp.zeros((16, 8), jnp.float32)},
            "fsdp", None, dims={"w": 0})
        return sh["w"], ef["w"], ok

    sm = shard_map(body, mesh=fsdp_mesh.mesh,
                   in_specs=(P("fsdp"),),
                   out_specs=(P("fsdp"), P(), P()), check_vma=False)
    sh, ef, ok = jax.jit(sm)(g)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(ef), 0.0)
    np.testing.assert_allclose(np.asarray(sh),
                               np.asarray(g).mean(0), rtol=1e-6)


def test_bucket_rs_striped_concurrent_wire(fsdp_mesh):
    """stripe=0.5: the lowered exchange carries BOTH an fp32 stripe and
    an int8 stripe (concurrent collectives on the two link classes),
    the stripe byte counters tick, and the result still reconstructs
    the mean within the quantized stripe's tolerance."""
    stats.reset("comm/")
    rs = np.random.RandomState(3)
    g = jnp.asarray(rs.randn(64, 16), jnp.float32)

    def body(gl):
        sh, ef, ok = C.quantized_bucket_reduce_scatter(
            {"w": gl}, {"w": jnp.zeros_like(gl)}, "fsdp", "int8",
            block=64, dims={"w": 0}, stripe=0.5, stripe_min=1)
        return sh["w"], ok

    sm = shard_map(body, mesh=fsdp_mesh.mesh, in_specs=(P(),),
                   out_specs=(P("fsdp"), P()), check_vma=False)
    jitted = jax.jit(sm)
    jx = jax.make_jaxpr(sm)(g)
    a2a = [(n, a) for n, a in _collective_eqns(jx) if n == "all_to_all"]
    # the int8 stripe AND a tensor-sized fp32 stripe (scales are tiny)
    assert any(a and a[0].dtype == jnp.int8 for _, a in a2a), a2a
    assert any(a and a[0].dtype == jnp.float32 and a[0].size > 16
               for _, a in a2a), a2a
    sh, ok = jitted(g)
    assert bool(ok)
    assert stats.get("comm/stripe_bytes_ici") > 0
    assert stats.get("comm/stripe_bytes_dcn") > 0
    bound = float(jnp.max(jnp.abs(g))) * (0.5 / 127) + 1e-6
    assert float(jnp.max(jnp.abs(sh - g))) <= bound


def test_bucket_rs_fp32_striped_two_concurrent_launches(fsdp_mesh):
    """Regression (review finding): striping on an fp32 wire must split
    into two CONCURRENT full-precision launches — it used to fall into
    the quantized branch and crash at trace time on method=None."""
    stats.reset("comm/")
    rs = np.random.RandomState(5)
    g = jnp.asarray(rs.randn(64, 16), jnp.float32)

    def body(gl):
        sh, ef, ok = C.quantized_bucket_reduce_scatter(
            {"w": gl}, {"w": jnp.zeros_like(gl)}, "fsdp", None,
            dims={"w": 0}, stripe=0.5, stripe_min=1)
        return sh["w"], ef["w"], ok

    sm = shard_map(body, mesh=fsdp_mesh.mesh, in_specs=(P(),),
                   out_specs=(P("fsdp"), P(), P()), check_vma=False)
    jx = jax.make_jaxpr(sm)(g)
    a2a = [(n, a) for n, a in _collective_eqns(jx) if n == "all_to_all"]
    assert len(a2a) == 2 and all(a[0].dtype == jnp.float32
                                 for _, a in a2a), a2a
    sh, ef, ok = jax.jit(sm)(g)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(ef), 0.0)   # exact wire
    np.testing.assert_allclose(np.asarray(sh), np.asarray(g), rtol=1e-6)
    assert stats.get("comm/stripe_bytes_ici") > 0
    assert stats.get("comm/stripe_bytes_dcn") > 0


def test_stripe_plan_and_resolve(monkeypatch):
    from paddle_tpu.cost_model import CostModel
    cm = CostModel(device_kind="v5")
    degrees = {"dp": 4, "fsdp": 2, "tp": 2}
    # single host → no second link class → no striping anywhere
    assert planner.stripe_plan(degrees, n_hosts=1, cost_model=cm) == {
        "dp": None, "fsdp": None}
    pol = planner.stripe_plan(degrees, n_hosts=4, cost_model=cm)
    # dp crosses hosts: fraction = q·B_dcn/(q·B_dcn + B_ici) ∈ (0,1)
    assert pol["fsdp"] is None
    assert 0.0 < pol["dp"] < 1.0
    eff = 3.94 * cm.dcn_bw
    assert pol["dp"] == pytest.approx(eff / (eff + cm.ici_bw), abs=1e-3)
    # knob resolution
    assert OV.resolve_stripe(0.3, "dp") == pytest.approx(0.3)
    assert OV.resolve_stripe("0", "dp") is None
    assert OV.resolve_stripe(1.5, "dp") is None     # out of range → off
    monkeypatch.delenv("PT_COMM_STRIPE", raising=False)
    assert OV.resolve_stripe(None, "dp") is None    # env default off
    # auto fraction tracks the RESOLVED wire format's compression
    monkeypatch.setenv("PT_COMM_STRIPE", "auto")
    monkeypatch.setenv("PT_NNODES", "2")
    degrees8 = {"fsdp": 8}

    class _M:  # duck-typed mesh: resolve_stripe only reads .shape
        shape = degrees8

    f_int8 = OV.resolve_stripe(None, "fsdp", _M, method="int8")
    f_fp32 = OV.resolve_stripe(None, "fsdp", _M, method=None)
    f_bf16 = OV.resolve_stripe(None, "fsdp", _M, method="bf16")
    assert f_int8 > f_bf16 > f_fp32 > 0, (f_int8, f_bf16, f_fp32)


# -- the overlap step ---------------------------------------------------------

def _collective_eqns(jaxpr):
    out = []

    def walk(jx):
        jx = getattr(jx, "jaxpr", jx)
        for eqn in jx.eqns:
            if eqn.primitive.name in ("all_gather", "all_to_all", "psum",
                                      "psum_scatter", "ppermute", "pmax"):
                out.append((eqn.primitive.name,
                            [v.aval for v in eqn.invars
                             if hasattr(v, "aval")]))
            for v in eqn.params.values():
                for cand in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(cand, "eqns") or hasattr(cand, "jaxpr"):
                        walk(cand)

    walk(jaxpr.jaxpr)
    return out


def _scan_bodies(jaxpr):
    """Body jaxprs of every scan, recursing through shard_map/pjit."""
    out = []

    def walk(jx):
        jx = getattr(jx, "jaxpr", jx)
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                out.append(eqn.params["jaxpr"])
            for v in eqn.params.values():
                for cand in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(cand, "eqns") or hasattr(cand, "jaxpr"):
                        walk(cand)

    walk(jaxpr.jaxpr)
    return out


def _body_stats(body):
    dots = a2a_q = ag_q = 0

    def walk(jx):
        nonlocal dots, a2a_q, ag_q
        jx = getattr(jx, "jaxpr", jx)
        for eqn in jx.eqns:
            n = eqn.primitive.name
            avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
            if n == "dot_general":
                dots += 1
            if n == "all_to_all" and avals and avals[0].dtype == jnp.int8:
                a2a_q += 1
            if n == "all_gather" and avals and avals[0].dtype == jnp.int8:
                ag_q += 1
            for v in eqn.params.values():
                for cand in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(cand, "eqns") or hasattr(cand, "jaxpr"):
                        walk(cand)

    walk(body)
    return dots, a2a_q, ag_q


def _make_step(mesh, **kw):
    params, stacked, emb, blk, lf = OV.mlp_block_model(n_layers=3)
    kw.setdefault("bucket_mb", 1e-4)
    return OV.overlap_parallel(
        dict(params), emb, blk, lf, optim.SGD(learning_rate=0.05),
        mesh, stacked, **kw)


def test_jaxpr_overlap_interleaves_collectives_into_backward(fsdp_mesh):
    """ACCEPTANCE: with overlap on, backward lowers to one quantized
    reduce-scatter per bucket (≥2 buckets on this model — the tiny
    budget gives one bucket per leaf, 3 total) INSIDE the scan body
    that also does the layer compute, and the stage-3 gather for layer
    l+1 is issued inside layer l's scan body (the forward scan carries
    the gathered full weights in its carry)."""
    x, y = _batch()
    sp, st, step = _make_step(fsdp_mesh.mesh, comm_quant="int8",
                              overlap=True)
    jx = jax.make_jaxpr(lambda p, s, a, b: step(p, s, a, b))(sp, st, x, y)
    bodies = _scan_bodies(jx)
    assert len(bodies) == 2, f"expected fwd+bwd scans, got {len(bodies)}"
    per_body = [_body_stats(b) for b in bodies]
    # the backward body: compute (dots) AND >=2 per-bucket quantized
    # reduce-scatters (all-to-all wire) in the SAME body
    bwd = [s for s in per_body if s[0] > 0 and s[1] >= 2]
    assert bwd, f"no scan body interleaves compute with bucket RS: " \
                f"{per_body}"
    # every scan body that computes also gathers (stage-3 prefetch path)
    for dots, _, ag in per_body:
        if dots:
            assert ag >= 1, per_body
    # the forward scan carries the gathered FULL weights (double
    # buffer): some scan body has a carry operand of a full per-layer
    # weight shape (d=16 × hidden=32) — the non-prefetch form only ever
    # carries activations
    carries = [tuple(v.aval.shape) for b in bodies
               for v in b.jaxpr.invars if hasattr(v, "aval")]
    assert (16, 32) in carries, carries


def test_jaxpr_overlap_off_keeps_tail_collective(fsdp_mesh):
    """The baseline lowers the OPPOSITE way: no scan body mixes layer
    compute with the bucket reduce-scatter — the collectives live in a
    separate tail scan (the fused-tail formulation)."""
    x, y = _batch()
    sp, st, step = _make_step(fsdp_mesh.mesh, comm_quant="int8",
                              overlap=False)
    jx = jax.make_jaxpr(lambda p, s, a, b: step(p, s, a, b))(sp, st, x, y)
    per_body = [_body_stats(b) for b in _scan_bodies(jx)]
    assert not any(s[0] > 0 and s[1] > 0 for s in per_body), per_body
    # the tail scan exists and carries the buckets
    assert any(s[0] == 0 and s[1] >= 2 for s in per_body), per_body


def test_overlap_toggle_bit_identical(fsdp_mesh):
    """Toggling overlap alone (prefetch pinned) is a scheduling-only
    change: parameters after 4 steps are BIT-identical."""
    x, y = _batch()
    out = {}
    for on in (True, False):
        sp, st, step = _make_step(fsdp_mesh.mesh, comm_quant="int8",
                                  overlap=on, prefetch=False)
        for _ in range(4):
            sp, st, loss = step(sp, st, x, y)
        out[on] = jax.device_get(sp)
    for k in out[True]:
        np.testing.assert_array_equal(np.asarray(out[True][k]),
                                      np.asarray(out[False][k]),
                                      err_msg=k)


def test_prefetch_toggle_ulp_parity(fsdp_mesh):
    """The double-buffered weight carry changes buffer layouts (and so
    the matmuls' FMA order) — parity there is float-ulp-level, not
    bitwise; pin the envelope."""
    x, y = _batch()
    out = {}
    for pf in (True, False):
        sp, st, step = _make_step(fsdp_mesh.mesh, comm_quant="int8",
                                  overlap=True, prefetch=pf)
        for _ in range(4):
            sp, st, loss = step(sp, st, x, y)
        out[pf] = jax.device_get(sp)
    for k in out[True]:
        a, b = np.asarray(out[True][k]), np.asarray(out[False][k])
        assert float(np.max(np.abs(a - b))) <= 1e-6, k


def _converge_in_child(method):
    """Body of test_overlap_step_converges, run in a child process (see
    there): prints one JSON line with the first/last loss and the
    error-feedback magnitude."""
    topo = dist.init_mesh(fsdp=4, devices=jax.devices()[:4],
                          set_global=False)
    _, st, losses = _run(topo.mesh, steps=30, comm_quant=method)
    ef_mag = max([float(jnp.max(jnp.abs(v)))
                  for v in st.get("comm_ef", {}).values()] or [0.0])
    print(json.dumps({"losses": losses, "ef_mag": ef_mag}))


@pytest.mark.parametrize("method", [None, "bf16", "int8"])
def test_overlap_step_converges(method):
    """30 steps must converge on every wire. Run in a CHILD process
    whose CPU programs run their collectives in order: XLA:CPU under
    jaxlib 0.9.0 runs independent collectives of one program
    concurrently under one rendezvous key and then ABORTS the process
    ("Unexpected number of participants", "id < num_threads", or a 40 s
    rendezvous timeout); the bf16 wire's program hit it on every run
    seen, at the seed of PR 21 too. The child therefore turns XLA:CPU's
    concurrency-optimized scheduler off (the program's thunks then run
    in one order on every device, which is what a TPU does anyway), and
    an abort that still happens is a failure this test reports and not
    a dead xdist worker."""
    code = (f"import sys; sys.path.insert(0, {os.path.dirname(__file__)!r}); "
            f"import test_comm_overlap as t; "
            f"t._converge_in_child({method!r})")
    env = dict(os.environ, XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_enable_concurrency_optimized_scheduler=false"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, (r.returncode, r.stderr[-1500:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    losses = out["losses"]
    assert losses[-1] < 0.2 * losses[0], losses
    if method == "int8":
        assert out["ef_mag"] > 0.0, "error feedback never engaged"


def test_loss_trajectory_parity_vs_quantized_step(fsdp_mesh):
    """ACCEPTANCE: the bucketed+prefetched step is loss-trajectory
    matched (PR 7 tolerances) with the established PR 7 quantized step
    on the SAME specs and the same flat loss — fp32 and int8."""
    params, stacked, emb, blk, lf = OV.mlp_block_model(n_layers=3)
    x, y = _batch()
    specs = OV.overlap_group_specs(dict(params), fsdp_mesh.mesh, stacked)

    def flat_loss(p, xb, yb):
        h = emb(p, xb, yb)
        for l in range(3):
            h = blk({k: p[k][l] for k in stacked}, h)
        return lf(p, h, xb, yb)

    def run_ref(method):
        opt = optim.SGD(learning_rate=0.05)
        sp, st = init_group_sharded_state(dict(params), opt, specs)
        if method:
            st = attach_comm_ef(dict(params), st, specs)
        step = build_group_sharded_step(flat_loss, opt, specs,
                                        comm_quant=method)
        losses = []
        for _ in range(40):
            sp, st, loss = step(sp, st, x, y)
            losses.append(float(loss))
        return losses

    for method in (None, "int8"):
        ref = run_ref(method)
        _, _, ov = _run(fsdp_mesh.mesh, steps=40, comm_quant=method)
        # PR 7's established convergence-parity tolerance
        assert ov[-1] <= ref[-1] * 1.5 + 1e-3, (method, ov[-1], ref[-1])
        assert ref[-1] <= ov[-1] * 1.5 + 1e-3, (method, ov[-1], ref[-1])
        # trajectories track each other step for step, not just at the end
        deltas = [abs(a - b) for a, b in zip(ov, ref)]
        assert max(deltas) <= 0.05 * ov[0] + 1e-3, (method, max(deltas))


def test_striped_step_trajectory_matches_unstriped(fsdp_mesh):
    _, _, base = _run(fsdp_mesh.mesh, steps=20, comm_quant="int8")
    _, _, striped = _run(fsdp_mesh.mesh, steps=20, comm_quant="int8",
                         stripe=0.5, stripe_min=1)
    assert striped[-1] <= base[-1] * 1.5 + 1e-3, (striped[-1], base[-1])


def test_overlap_group_specs_layer_dim_never_sharded(fsdp_mesh):
    params, stacked, *_ = OV.mlp_block_model(n_layers=4)
    specs = OV.overlap_group_specs(dict(params), fsdp_mesh.mesh, stacked)
    for k in stacked:
        for tree in (specs.param, specs.grad, specs.opt_slot):
            entry = tuple(tree[k])
            assert entry and entry[0] is None, (k, entry)
            assert any(e == "fsdp" or (isinstance(e, tuple) and
                                       "fsdp" in e)
                       for e in entry[1:]), (k, entry)


def test_os_g_level_runs_and_converges(fsdp_mesh):
    _, _, losses = _run(fsdp_mesh.mesh, steps=20, comm_quant="int8",
                        level="os_g")
    assert losses[-1] < 0.3 * losses[0], losses


def test_overlap_env_contract_declared():
    for name in ("PT_COMM_BUCKET_MB", "PT_COMM_OVERLAP",
                 "PT_COMM_STRIPE"):
        assert pt_flags.env_declared(name), name
