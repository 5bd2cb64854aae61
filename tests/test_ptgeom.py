"""ptgeom (ISSUE 20) — static kernel-geometry verification.

Per-rule fixtures for PT006–PT009 over hand-built KernelSpecs, the
inline-suppression and baseline round-trips, harvest parity against
hand-computed block bytes for the paged append+attend, the planted over-budget
kernel the CLI must catch BY NAME, the repo self-sweep zero-new gate,
and the autotune geometry-refusal contract.

Everything traces under ``jax.eval_shape`` (CPU, nothing executes), so
the whole file stays tier-1 fast.
"""

import functools
import importlib.util
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

from paddle_tpu.analysis import baseline, engine, rules_tpu
from paddle_tpu.analysis import kernelmodel as km

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PTGEOM = os.path.join(REPO, "tools", "ptgeom.py")


# -- fixture helpers ---------------------------------------------------------

def _project(tmp_path, src=None):
    d = tmp_path / "pkg"
    d.mkdir(exist_ok=True)
    (d / "k.py").write_text(src or ("x = 1\n" * 30))
    return engine.load_project([str(d)], root=str(tmp_path))


def _op(role="in", index=0, shape=(1024, 1024), dtype="float32",
        block=(128, 128), space="vmem", deps=None, probes=None,
        map_id=None):
    return km.OperandSpec(role=role, index=index, shape=shape,
                          dtype=dtype, block=block, space=space,
                          deps=deps, probes=probes or {},
                          map_id=map_id)


def _spec(line=3, **kw):
    defaults = dict(body="kern", path="pkg/k.py", abspath="", line=line,
                    grid=(4,), num_scalar_prefetch=0, inputs=[],
                    outputs=[], scratch=[], aliases={}, kernel="kern",
                    geometry="tiny", config="c0")
    defaults.update(kw)
    return km.KernelSpec(**defaults)


def _run(tmp_path, specs, src=None, rules=None):
    project = _project(tmp_path, src)
    project.geom_specs = specs
    return engine.run(project, rules or rules_tpu.geom_rules())


def _rules_hit(findings):
    return {f.rule for f in findings}


# -- PT006: VMEM budget ------------------------------------------------------

def test_pt006_over_budget_names_worst_geometry(tmp_path):
    small = _spec(geometry="tiny", config="bk128",
                  inputs=[_op(block=(128, 128))])
    big = _spec(geometry="r06", config="bk4096",
                inputs=[_op(shape=(8192, 8192), block=(4096, 4096))])
    findings = _run(tmp_path, [small, big])
    f = [f for f in findings if f.rule == "PT006"]
    assert len(f) == 1
    assert "kern" in f[0].message and f[0].severity == "error"
    # the worst (geometry, config) pair is named, not just the site
    assert "r06" in f[0].message and "bk4096" in f[0].message


def test_pt006_within_budget_clean(tmp_path):
    spec = _spec(inputs=[_op(block=(256, 512))],
                 outputs=[_op(role="out", block=(256, 512))],
                 scratch=[km.ScratchSpec(shape=(256, 512),
                                         dtype="float32")])
    assert "PT006" not in _rules_hit(_run(tmp_path, [spec]))


def test_vmem_estimate_double_buffers_and_skips_aliased():
    blocked = _op(index=0, block=(128, 128))              # 64 KiB
    aliased = _op(index=1, block=(128, 128))
    anyspace = _op(index=2, block=None, space="any",
                   shape=(1 << 20,))
    out = _op(role="out", index=0, block=(128, 128))
    spec = _spec(inputs=[blocked, aliased, anyspace], outputs=[out],
                 aliases={1: 0},
                 scratch=[km.ScratchSpec(shape=(128, 128),
                                         dtype="float32")])
    # 2 blocked (1 in + 1 out) x 64 KiB x double-buffer + scratch;
    # the aliased input shares the output's buffer, ANY stays in HBM
    want = 2 * (128 * 128 * 4) * km.DOUBLE_BUFFER + 128 * 128 * 4
    assert km.vmem_estimate(spec) == want


# -- PT007: tiling alignment -------------------------------------------------

def test_pt007_sublane_and_lane_misalignment(tmp_path):
    spec = _spec(inputs=[_op(index=0, block=(100, 128)),     # sublane
                         _op(index=1, block=(128, 120))])    # lane
    f = [f for f in _run(tmp_path, [spec]) if f.rule == "PT007"]
    assert len(f) == 1
    assert "sublane" in f[0].message and "lane" in f[0].message
    # a 1-row block of a many-row array: the v5e lowering REFUSED
    # exactly this on stacked LN/bias vectors (PR 21)
    row = _spec(inputs=[_op(index=0, shape=(24, 2048), block=(1, 2048),
                            dtype="bfloat16")])
    f = [f for f in _run(tmp_path, [row]) if f.rule == "PT007"]
    assert len(f) == 1 and "sublane" in f[0].message


def test_pt007_aligned_and_full_dims_clean(tmp_path):
    spec = _spec(inputs=[
        _op(index=0, block=(128, 512)),
        # trailing dim == full array extent: not a chosen tile
        _op(index=1, shape=(24, 96), block=(8, 96)),
        # row-streaming done right: a (1, 1, n) block of an (L, 1, n)
        # array — the last two block dims ARE the array's
        _op(index=2, shape=(24, 1, 2048), block=(1, 1, 2048),
            dtype="bfloat16"),
    ])
    assert "PT007" not in _rules_hit(_run(tmp_path, [spec]))


# -- PT008: aliasing contracts -----------------------------------------------

def test_pt008_unaliased_any_pool(tmp_path):
    pool_in = _op(index=0, shape=(64, 2, 128, 32), block=None,
                  space="any")
    pool_out = _op(role="out", index=0, shape=(64, 2, 128, 32),
                   block=None, space="any")
    spec = _spec(inputs=[pool_in], outputs=[pool_out], aliases={})
    f = [f for f in _run(tmp_path, [spec]) if f.rule == "PT008"]
    assert len(f) == 1 and "not input_output_aliased" in f[0].message


def test_pt008_aliased_pool_clean(tmp_path):
    pool_in = _op(index=0, shape=(64, 2, 128, 32), block=None,
                  space="any")
    pool_out = _op(role="out", index=0, shape=(64, 2, 128, 32),
                   block=None, space="any")
    spec = _spec(inputs=[pool_in], outputs=[pool_out], aliases={0: 0})
    assert "PT008" not in _rules_hit(_run(tmp_path, [spec]))


def test_pt008_diverging_index_maps(tmp_path):
    inp = _op(index=0, block=(128, 128), deps=(0,),
              probes={(1,): (1, 0)}, map_id=1)
    outp = _op(role="out", index=0, block=(128, 128), deps=(0,),
               probes={(1,): (2, 0)}, map_id=2)
    spec = _spec(inputs=[inp], outputs=[outp], aliases={0: 0})
    f = [f for f in _run(tmp_path, [spec]) if f.rule == "PT008"]
    assert len(f) == 1 and "diverge" in f[0].message


def test_pt008_same_map_object_shortcut(tmp_path):
    # identical map_id (the paged fused path reuses ONE index-map
    # callable for the aliased pair) short-circuits the probe compare
    inp = _op(index=0, block=(128, 128), deps=None, map_id=7)
    outp = _op(role="out", index=0, block=(128, 128), deps=None,
               map_id=7)
    spec = _spec(inputs=[inp], outputs=[outp], aliases={0: 0})
    assert "PT008" not in _rules_hit(_run(tmp_path, [spec]))


# -- PT009: grid-cost sanity -------------------------------------------------

def test_pt009_reread_flagged(tmp_path):
    # grid (8, 4) row-major; operand depends only on the LAST grid dim:
    # fetched 32x, 4 distinct blocks -> 8x re-read, 28 extra fetches
    op = _op(index=0, shape=(1024, 1024), block=(128, 128), deps=(1,))
    spec = _spec(grid=(8, 4), inputs=[op])
    f = [f for f in _run(tmp_path, [spec]) if f.rule == "PT009"]
    assert len(f) == 1
    assert "8x re-read" in f[0].message


def test_pt009_streaming_and_small_rereads_clean(tmp_path):
    spec = _spec(grid=(8, 4), inputs=[
        # depends on the trailing dim's run: fetched once per step but
        # every block distinct (normal streaming)
        _op(index=0, block=(128, 128), deps=(0, 1)),
        # constant map: one block, fetched once (suffix run covers all)
        _op(index=1, block=(128, 128), deps=()),
        # re-read but tiny: a (8, 128) f32 scale strip stays under the
        # PT009_MIN_EXTRA_BYTES floor
        _op(index=2, shape=(64, 1024), block=(8, 128), deps=(1,)),
        # data-dependent map (scalar-prefetch driven): unanalyzable
        _op(index=3, block=(128, 128), deps=None),
    ])
    assert "PT009" not in _rules_hit(_run(tmp_path, [spec]))


# -- suppression + baseline --------------------------------------------------

def test_inline_suppression_at_launch_site(tmp_path):
    src = ("x = 1\n"
           "# ptlint: disable=PT006 -- planted slab, see docs\n"
           "y = 2\n")
    spec = _spec(line=3,
                 inputs=[_op(shape=(8192, 8192), block=(4096, 4096))])
    assert "PT006" not in _rules_hit(_run(tmp_path, [spec], src=src))


def test_geom_baseline_roundtrip(tmp_path):
    spec = _spec(inputs=[_op(shape=(8192, 8192), block=(4096, 4096))])
    findings = _run(tmp_path, [spec])
    assert findings
    bl = tmp_path / "geom_baseline.json"
    baseline.write(str(bl), findings)
    new, known = baseline.partition(findings, baseline.load(str(bl)))
    assert not new and len(known) == len(findings)


# -- harvest parity ----------------------------------------------------------

def test_paged_append_attend_harvest_parity_hand_computed():
    """`paged_append_attend` at tiny geometry, 8 rows over a 2-column
    table: two launches (the row write, then the attend), whose
    harvested specs must agree with hand-computed grid/prefetch/alias/
    block facts."""
    from paddle_tpu.ops.pallas.paged_attention import (
        _vmem_bytes, paged_append_attend)
    p = km.LADDER["tiny"]
    dm, hq, hkv = p["dm"], p["heads"], p["kv_heads"]
    d, dt, page, B, cols = dm // hq, p["dtype"], p["page"], 8, 2
    q = km.sds((B, hq, d), dt)
    pool = km.sds((B * cols + 1, hkv, page, d), dt)
    row = km.sds((B, hkv, d), dt)
    table = km.sds((B, cols), "int32")
    vec = km.sds((B,), "int32")

    specs = km.harvest(
        lambda: jax.eval_shape(paged_append_attend, q, pool, pool, row,
                               row, table, vec, vec),
        root=REPO)
    assert [s.body for s in specs] == ["_write_kernel", "_kernel"]
    write, attend = specs
    view = ((B * cols + 1) * hkv, page, d)     # the pools, head-major
    sub = 8                                    # float32 sublane tile
    for spec in specs:
        # both heads of a page fit VMEM: one program a row, lengths and
        # the table (or the write's page ids) prefetched as scalars
        assert spec.grid == (B,)
        assert spec.num_scalar_prefetch == 2
        assert spec.path == "paddle_tpu/ops/pallas/paged_attention.py"
    # the write: each pool in once, aliased to its output (in place),
    # and what moves is the sublane tile that holds the row
    assert write.aliases == {4: 0, 5: 1}
    for gi, go in write.aliases.items():
        inp = next(op for op in write.inputs if op.index == gi)
        out = next(op for op in write.outputs if op.index == go)
        assert inp.shape == out.shape == view
        assert inp.block == out.block == (hkv, sub, d)
        assert inp.block_bytes() == hkv * sub * d * 4
    # the attend: pools stay in HBM, unblocked and unaliased; a page's
    # heads land in one of two buffers a pool
    assert not attend.aliases
    pools = [op for op in attend.inputs if op.shape == view]
    assert len(pools) == 2
    assert all(op.space == "any" and op.block is None for op in pools)
    landing = [sc for sc in attend.scratch
               if sc.shape == (2, hkv, page, d)]
    assert len(landing) == 2
    assert km.vmem_estimate(attend) <= _vmem_bytes(hkv, page, d, dt, 1)
    assert km.vmem_estimate(attend) <= km.vmem_budget_bytes()


@pytest.mark.parametrize("geom", sorted(km.LADDER))
def test_paged_default_geometry_is_one_program_a_row(geom):
    """With no head block named (what the engine and the benchmark's
    serving cells run), both launches of `paged_append_attend` take all
    of a page's KV heads a program at every rung of the ladder: 16
    programs for 16 rows, two landing buffers a pool, inside the VMEM
    budget. Nothing but the shapes decides it."""
    from paddle_tpu.ops.pallas.paged_attention import (
        _default_head_block, paged_append_attend)
    p = km.LADDER[geom]
    hkv, d, page, dt, B = (p["kv_heads"], p["dm"] // p["heads"], p["page"],
                           p["dtype"], 16)
    cols = max(1, p["seq"] // page)
    q = km.sds((B, p["heads"], d), dt)
    pool = km.sds((B * cols + 1, hkv, page, d), dt)
    row = km.sds((B, hkv, d), dt)
    table, vec = km.sds((B, cols), "int32"), km.sds((B,), "int32")
    assert _default_head_block(page, hkv, d, dt,
                               p["heads"] // hkv) == hkv
    write, attend = km.harvest(
        lambda: jax.eval_shape(paged_append_attend, q, pool, pool, row,
                               row, table, vec, vec),
        root=REPO)
    assert write.grid == attend.grid == (B,)
    assert [sc.shape for sc in attend.scratch[:2]] == [
        (2, hkv, page, d)] * 2
    assert km.vmem_estimate(attend) <= km.vmem_budget_bytes()


# -- CLI ---------------------------------------------------------------------

HOG_SRC = '''
import jax
from jax.experimental import pallas as pl


def _copy(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def vmem_hog(x):
    return pl.pallas_call(
        _copy,
        grid=(4,),
        in_specs=[pl.BlockSpec((4096, 4096), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((4096, 4096), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)


def ptgeom_cases():
    from paddle_tpu.analysis import kernelmodel as km

    def run():
        jax.eval_shape(vmem_hog, km.sds((4096, 4096), "float32"))
    return [km.GeomCase(kernel="vmem_hog", geometry="tiny",
                        config="full", run=run)]
'''


def test_cli_catches_planted_over_budget_kernel(tmp_path):
    hog = tmp_path / "hog_kernels.py"
    hog.write_text(HOG_SRC)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PTGEOM_GEOMS", None)
    proc = subprocess.run(
        [sys.executable, PTGEOM, "--extra", str(hog),
         "--kernels", "vmem_hog", "--no-table"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = proc.stdout + proc.stderr
    assert "vmem_hog" in out and "PT006" in out


def _ptgeom_main():
    spec = importlib.util.spec_from_file_location("_ptgeom_cli", PTGEOM)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def test_repo_self_sweep_zero_new_findings(monkeypatch, capsys):
    """The shipped tree must sweep clean: every deliberate geometry
    fact carries an inline rationale, the baseline stays EMPTY."""
    monkeypatch.delenv("PTGEOM_GEOMS", raising=False)
    monkeypatch.delenv("PT_VMEM_BUDGET_MB", raising=False)
    rc = _ptgeom_main()(["--no-table", "--stats"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "baselined: 0" in out


# -- autotune refusal --------------------------------------------------------

def test_autotune_geom_check_refuses_before_building(tmp_path):
    from paddle_tpu.ops.pallas import autotune as at
    cache = at.AutotuneCache(path=str(tmp_path / "cache.json"))
    built = []

    def build_and_run(cfg):
        built.append(cfg)

    def geom_check(cfg):
        return "PT006: slab over budget" if cfg == 128 else None

    best, timings = at.tune("k", "key1", [128, 64], build_and_run,
                            warmup=0, iters=1, cache=cache,
                            geom_check=geom_check)
    assert best == 64
    assert 128 not in built          # refused candidates never build
    assert 128 not in {c for c in timings}

    with pytest.raises(ValueError, match="geometry-refused"):
        at.tune("k", "key2", [128], build_and_run, cache=cache,
                geom_check=geom_check)
