"""Execute every ``bench_*`` function in bench.py on tiny CPU shapes.

VERDICT r2 weak 1: ``bench_resnet50`` crashed on the driver's TPU run
because it called an API whose contract had drifted, and no test could
catch it — the function returned ``{}`` early on CPU. These smoke tests run
the SAME code paths (split_params/merge_params/stateful-context/optimizer/
compile) with smoke=True so API drift fails here first.
"""

import sys
import os

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402

PEAK = 1e12  # nominal; only affects reported ratios, not execution


def test_bench_gpt_cpu_path():
    # on the CPU the flagship row is an ERROR unless the caller asks
    # for the smoke size: a gpt_tiny CPU timing never stands in for it
    with pytest.raises(RuntimeError, match="accelerator"):
        bench.bench_gpt(jax, jnp, PEAK)
    res = bench.bench_gpt(jax, jnp, PEAK, smoke=True)
    assert res["metric"] == "gpt_tiny_tokens_per_sec_per_chip"
    assert res["value"] > 0
    # bench_decode depends on this attribute being set
    assert getattr(bench.bench_gpt, "model", None) is not None


def test_bench_decode_smoke():
    if getattr(bench.bench_gpt, "model", None) is None:
        bench.bench_gpt(jax, jnp, PEAK, smoke=True)
    out = bench.bench_decode(jax, jnp, PEAK, smoke=True)
    assert any(k.startswith("decode_") and k.endswith("_tokens_per_sec")
               for k in out), out
    # the continuous-batching engine path must run clean in smoke mode
    assert "decode_engine_tokens_per_sec" in out, out
    assert out.get("decode_engine_vs_roofline", 0) > 0, out
    # ...and so must the speculative path (its own try/except means a
    # regression would otherwise vanish silently)
    assert out.get("decode_spec_tokens_per_step", 0) > 0, out
    # paged-spec row (ISSUE 19) — the r05 row death must fail here
    # first; its launches scale with layers
    assert out.get("decode_spec_paged_tokens_per_step", 0) > 0, out
    assert out.get("decode_spec_paged_launches_per_step", 0) > 0, out
    # kernel-launch ladder row present on the engine path too
    assert "decode_engine_launches_per_token" in out, out


def test_bench_serve_smoke():
    """BENCH_SERVE ladder (ISSUE 10): the deterministic load generator
    must drive the front-end through every rung, and at sub-saturation
    QPS the scheduler must keep the pipeline fed (fed-occupancy well
    above the 1/slots trickling-singletons floor)."""
    out = bench.bench_serve(jax, jnp, PEAK, smoke=True)
    assert out.get("serve_capacity_tokens_per_sec", 0) > 0, out
    for rung in ("sub25", "sub75", "over2x"):
        assert out.get(f"serve_{rung}_p99_ttft_ms", 0) > 0, (rung, out)
        assert out.get(f"serve_{rung}_goodput_tokens_per_sec", 0) > 0, \
            (rung, out)
        assert out.get(f"serve_{rung}_completed_frac", 0) == 1.0, \
            (rung, out)
    # sub-saturation occupancy floor: when demand exceeded free slots,
    # slots were actually filled (trickling singletons would sit at
    # 1/slots = 0.25 here)
    fed = out.get("serve_sub75_fed_occupancy_mean")
    assert fed is not None and fed >= 0.5, out
    assert out.get("serve_over2x_fed_occupancy_mean", 0) >= 0.5, out
    # sustained backlog must trigger retire-time backfill
    assert out.get("serve_over2x_backfills", 0) > 0, out


def test_bench_serve_disagg_smoke():
    """Disaggregated-serving ladder row (ISSUE 12): both the symmetric
    baseline and the prefill→wire→decode pair must serve the full
    over-saturation workload, the KV transfer must actually compress
    (≥3.5x on the int8 default), and the fleet prefix tail must hit
    cross-replica."""
    out = bench.bench_serve_disagg(jax, jnp, PEAK, smoke=True)
    for label in ("symmetric", "disagg"):
        assert out.get(
            f"serve_disagg_{label}_goodput_tokens_per_sec", 0) > 0, out
        assert out.get(
            f"serve_disagg_{label}_completed_frac", 0) == 1.0, out
        assert out.get(f"serve_disagg_{label}_p99_ttft_ms", 0) > 0, out
    # role-tagged TTFT (ISSUE 13): the prefill engine's first-token
    # samples land in their own serve/prefill_s histogram — present in
    # the disagg row — and never pollute the end-to-end TTFT p99
    assert out.get("serve_disagg_prefill_p99_ms", 0) > 0, out
    assert out.get("serve_disagg_kv_bytes_wire", 0) > 0, out
    assert out.get("serve_disagg_kv_ratio") is not None
    assert out["serve_disagg_kv_ratio"] >= 3.5, out
    assert out.get("serve_disagg_kv_transfer_p99_ms", 0) > 0, out
    assert out.get("serve_disagg_fleet_hit_tokens", 0) > 0, out


def test_bench_fleet_churn_smoke():
    """Fleet-churn ladder row (ISSUE 14): both phases must serve every
    request (the kill's unfinished work redistributes, at-least-once),
    the churn phase must actually have redistributed something, and
    the goodput ratio must be computable."""
    out = bench.bench_fleet_churn(jax, jnp, PEAK, smoke=True)
    for label in ("steady", "churn"):
        assert out.get(
            f"fleet_churn_{label}_goodput_tokens_per_sec", 0) > 0, out
        assert out.get(
            f"fleet_churn_{label}_completed_frac", 0) == 1.0, out
        assert out.get(f"fleet_churn_{label}_p99_ttft_ms", 0) > 0, out
    assert out.get("fleet_churn_redistributed", 0) > 0, out
    assert out.get("fleet_churn_goodput_ratio", 0) > 0, out
    # drain-with-migration phase (ISSUE 16): the drain must have moved
    # live requests, completed everything, and bounded its latency
    assert out.get("fleet_churn_drain_completed_frac", 0) == 1.0, out
    assert out.get("fleet_churn_drain_migrated", 0) > 0, out
    assert out.get("fleet_churn_drain_latency_ms", -1) >= 0, out
    assert "fleet_churn_drain_goodput_dip_frac" in out, out
    # router-failover phase (ISSUE 17): journal replay must complete
    # every request (zero id loss through the simulated router death)
    # with a measurable, bounded recovery
    assert out.get("fleet_churn_failover_completed_frac", 0) == 1.0, out
    assert out.get("fleet_churn_failover_goodput_tokens_per_sec",
                   0) > 0, out
    assert out.get("fleet_churn_failover_recovery_s", -1) >= 0, out
    assert out.get("fleet_churn_failover_republished", -1) >= 0, out
    assert "fleet_churn_failover_goodput_dip_frac" in out, out
    # reshape wall-clock rows (in-HBM vs checkpoint round trip) appear
    # whenever >= 4 devices are visible (conftest forces 8 on CPU)
    if len(jax.devices()) >= 4:
        assert out.get("fleet_churn_reshard_inplace_ms", 0) > 0, out
        assert out.get("fleet_churn_reshard_ckpt_ms", 0) > 0, out


def test_bench_train_quant_comm_smoke():
    out = bench.bench_train_quant_comm(jax, jnp, PEAK, smoke=True)
    assert out.get("train_quant_comm_fp32_step_ms", 0) > 0, out
    assert out.get("train_quant_comm_int8_step_ms", 0) > 0, out
    # the loss trajectory must stay glued to the fp32 run at fixed seed
    assert abs(out.get("train_quant_comm_int8_loss_delta", 1)) < 0.1, out
    # and the wire must actually be narrow (int8 block-256 acceptance)
    assert out.get("train_quant_comm_int8_wire_ratio", 0) >= 3.5, out


def test_bench_train_overlap_smoke():
    out = bench.bench_train_overlap(jax, jnp, PEAK, smoke=True)
    for name in ("fp32_on", "fp32_off", "int8_on", "int8_off"):
        assert out.get(f"train_overlap_{name}_step_ms", 0) > 0, out
    # overlap on vs off must be trajectory-matched (same math, only the
    # collective schedule moves)
    assert abs(out.get("train_overlap_fp32_loss_delta", 1)) < 1e-5, out
    assert abs(out.get("train_overlap_int8_loss_delta", 1)) < 1e-4, out
    # the span-tracer accounting made it into the row, with real
    # collective issue spans measured (multi-device conftest mesh)
    assert 0.0 <= out["train_overlap_overlap_frac"] <= 1.0, out
    assert out["train_overlap_comm_busy_s"] > 0, out
    assert out["train_overlap_exposed_s"] >= 0, out


def test_bench_train_numerics_smoke():
    out = bench.bench_train_numerics(jax, jnp, PEAK, smoke=True)
    for name in ("off", "every1", "every16"):
        assert out.get(f"train_numerics_{name}_step_ms", 0) > 0, out
    assert "train_numerics_overhead_frac" in out, out
    # parity: the in-graph stats never feed back into the update
    assert abs(out.get("train_numerics_loss_delta", 1)) < 1e-6, out


def test_bench_train_sharded_stacked_smoke():
    out = bench.bench_train_sharded_stacked(jax, jnp, PEAK, smoke=True)
    assert out.get("train_sharded_stacked_per_layer_step_ms", 0) > 0, out
    assert out.get("train_sharded_stacked_stacked_step_ms", 0) > 0, out
    # fixed-seed parity: stacked is the SAME program, just pre-stacked
    assert abs(out.get("train_sharded_stacked_loss_delta", 1)) < 1e-4, out


def test_bench_bert_smoke():
    out = bench.bench_bert(jax, jnp, PEAK, smoke=True)
    assert out["bert_base_tokens_per_sec_per_chip"] > 0
    assert "bert_base_mfu" in out


def test_bench_resnet50_smoke():
    out = bench.bench_resnet50(jax, jnp, PEAK, smoke=True)
    assert out["resnet50_imgs_per_sec"] > 0
    assert out["resnet50_batch"] == 2


def test_bench_ppyoloe_smoke():
    out = bench.bench_ppyoloe(jax, jnp, PEAK, smoke=True)
    assert out["ppyoloe_s_imgs_per_sec"] > 0
    assert out["ppyoloe_s_batch"] == 2
    # the one-program eval path (forward + jit matrix-NMS) must run clean
    assert out.get("ppyoloe_s_eval_imgs_per_sec", 0) > 0, out


def test_bench_pp_smoke():
    out = bench.bench_pp(jax, jnp, PEAK, smoke=True)
    assert out["pp2_step_ms"] > 0 and out["pp2_dense_step_ms"] > 0
    assert 0 < out["pp2_bubble_theoretical"] < 1


def test_bench_longctx_smoke():
    out = bench.bench_longctx(jax, jnp, PEAK, smoke=True)
    assert out.get("longctx_64_tokens_per_sec", 0) > 0, out
    assert "longctx_64_mfu" in out


def test_bench_nonsmoke_cpu_guards():
    # driver-mode guards: on CPU the TPU-only sub-benches stay silent
    assert bench.bench_bert(jax, jnp, PEAK) == {}
    assert bench.bench_resnet50(jax, jnp, PEAK) == {}
    assert bench.bench_ppyoloe(jax, jnp, PEAK) == {}
    assert bench.bench_pp(jax, jnp, PEAK) == {}
    assert bench.bench_longctx(jax, jnp, PEAK) == {}
    assert bench.bench_train_sharded_stacked(jax, jnp, PEAK) == {}
    assert bench.bench_train_overlap(jax, jnp, PEAK) == {}
    assert bench.bench_serve_disagg(jax, jnp, PEAK) == {}
    assert bench.bench_train_numerics(jax, jnp, PEAK) == {}


def test_split_params_contract():
    """The (params, buffers) contract bench_resnet50 relies on."""
    from paddle_tpu.vision.models import resnet18
    net = resnet18(num_classes=10)
    params, buffers = net.split_params()
    assert isinstance(buffers, dict)
    # BN running stats are buffers, not trainable params
    assert any("_mean" in k or "mean" in k for k in buffers), \
        list(buffers)[:5]
    assert not (set(params) & set(buffers))
    merged = net.merge_params({**buffers, **params})
    assert merged is not net
