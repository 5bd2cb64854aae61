"""The latent-attention, sparse-expert configuration's cell at a tiny size
on the CPU: the new kind end to end (sound, with a token altered, and
with each control one precision below the configuration's in the
program's place), the new counts of work against hand sums, the
configuration against its source, the pool against the mix, and every new
per-layer metric silent where there is nothing to read."""

import copy
import json

import jax
import numpy as np
import pytest

from benchmark import harness, peaks, run as runner, trace_reduce
from benchmark import work_latent_moe as work

CELL = "joyai-llm-flash.serve-doc32"
SEED = 2**31 + 2035
TINY = {
    "n_layers": 3, "leading_dense": 1, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 16, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "d_ffn": 128, "n_experts": 32, "experts_per_token": 4,
    "expert_width": 32, "n_shared_experts": 1, "routed_scaling": 2.5,
    "vocab_size": 512, "max_seq_len": 1024, "rope_theta": 10000.0,
    "norm_eps": 1e-06, "use_bias": False, "tie_embeddings": False,
    "dtype": "float32",
}
TINY_LIMITS = {"logit_gap": 1e-3, "routing_mismatch": 0.01,
               "router_mismatch": 0.01, "unfinished": 0}
NEW_METRICS = ("step_mfu.serve_latent_moe", "moe_experts_roofline.serve",
               "latent_attend_roofline.serve", "moe_time_share.serve")


@pytest.fixture
def tiny_cell():
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell["model"] = dict(TINY)
    cell["limits"] = dict(TINY_LIMITS)
    cell["traffic"].update(
        clients=3, max_slots=3, request_pool=8, checked_requests=3,
        prefill_chunk=128, kv_pool_pages=3 * 4, traced_seconds=1,
        prompt_len={"dist": "uniform", "min": 100, "max": 400},
        answer_len={"dist": "uniform", "min": 4, "max": 12})
    return cell


def _execute(cell, capsys, seconds=2.0):
    result = runner.execute(cell, SEED, seconds, False, jax,
                            jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return result, line


def test_cell_is_correct(tiny_cell, capsys):
    result, line = _execute(tiny_cell, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "serve_ttft_p50_ms", "setup_s"}
    assert set(line["compared"]) == set(TINY_LIMITS)
    # the routing compared is what the engine reported of the run
    assert line["compared"]["routing_mismatch"]["value"] == 0
    assert line["compared"]["router_mismatch"]["value"] == 0
    assert result["counters"]["compiled_in_window"] == 0
    longest = max(n for _, n in result["counters"]["prefills"])
    assert longest > 2 * tiny_cell["traffic"]["prefill_chunk"]


def test_altered_token_is_not_correct(tiny_cell, capsys, monkeypatch):
    from paddle_tpu.serving import scheduler
    real = scheduler.FrontEnd._on_token
    vocab = TINY["vocab_size"]

    def altered(self, ereq, token):
        if len(ereq.tokens) == 3:
            token = (token + vocab // 2) % vocab
        return real(self, ereq, token)

    monkeypatch.setattr(scheduler.FrontEnd, "_on_token", altered)
    _, line = _execute(tiny_cell, capsys)
    assert line["correct"] is False
    assert line["compared"]["logit_gap"]["value"] \
        > line["compared"]["logit_gap"]["limit"]


@pytest.fixture(scope="module")
def served():
    """A few requests served by the engine at the tiny size, as the
    kind's checked samples have them (those of the slot whose routing
    the engine reports carry it), with the weights they were served
    on."""
    from benchmark.kinds import serve_latent_moe as serve
    from paddle_tpu import inference, serving
    weights = serve.latent_moe.make_weights(TINY, SEED)
    eng = inference.make_engine(serve.build_model(TINY, weights),
                                max_slots=2, n_pages=8, prefill_chunk=128)
    fe = serving.FrontEnd(eng)
    tap = serve.RoutingTap(eng)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, TINY["vocab_size"], n).tolist()
               for n in (150, 330, 90)]
    reqs = [fe.submit(p, max_new_tokens=10) for p in prompts]
    fe.run()
    cell = {"model": TINY, "traffic": {
        "answer_len": {"dist": "fixed", "value": 10}, "request_pool": 1}}
    finished = [{"prompt": p, "tokens": list(r.tokens), "complete": True}
                for p, r in zip(prompts, reqs)]
    samples = serve.checked_samples(finished, 3, SEED, tap)
    return serve, cell, weights, samples


def test_checked_samples_hold_the_longest_and_the_reported(served):
    serve, _, _, samples = served
    assert len(samples) == 3
    assert max(len(s["prompt"]) for s in samples) == 330
    reported = [s for s in samples if "routing" in s]
    assert len(reported) == 2           # the reported slot served two
    for s in reported:
        n0, routing = len(s["prompt"]), s["routing"]
        assert list(routing["at"]) == list(range(n0, n0 + 9))
        assert routing["saw"].shape == (9, 2, TINY["d_model"])
        assert routing["chose"].shape == (9, 2, TINY["experts_per_token"])


@pytest.mark.parametrize("control", [
    {}, {"cache": "float8"}, {"router": "bfloat16"}, {"mode": "fp8"},
    {"mode": "int8"}], ids=lambda c: "-".join(c.values()) or "program")
def test_a_precision_below_the_configurations_fails(served, control):
    """By one of the cell's limits, not by each: a control whose first
    choice happens to be the reference's over these few tokens still
    routes its tokens differently; only a lower precision of the router
    itself moves ``router_mismatch``."""
    serve, cell, weights, samples = served
    checked = serve.reference_gaps(cell, weights, samples, **control)
    over = {name for name, value, limit in serve.compare(
        checked, TINY_LIMITS) if value > limit}
    assert bool(over) == bool(control)      # the program itself passes
    assert ("router_mismatch" in over) == (control == {"router": "bfloat16"})


def test_a_served_router_below_float32_fails(served, monkeypatch):
    """What the served path reports is what it did: the program's router
    computed in bfloat16 serves tokens whose reported choices are not
    the float32 router's for the tokens it saw."""
    from paddle_tpu import inference, serving
    from paddle_tpu.models import expert_layer
    serve, cell, weights, _ = served

    def route_bf16(x, w_router, bias, per_token, scale):
        return serve.latent_moe.choose_experts(
            x, w_router, bias, per_token, scale, router="bfloat16")

    monkeypatch.setattr(expert_layer, "route", route_bf16)
    eng = inference.make_engine(serve.build_model(TINY, weights),
                                max_slots=1, n_pages=4, prefill_chunk=128)
    fe, tap = serving.FrontEnd(eng), serve.RoutingTap(eng)
    prompt = np.random.default_rng(6).integers(
        0, TINY["vocab_size"], 200).tolist()
    req = fe.submit(prompt, max_new_tokens=40)
    fe.run()
    finished = [{"prompt": prompt, "tokens": list(req.tokens),
                 "complete": True}]
    cell = dict(cell, traffic={"answer_len": {"dist": "fixed", "value": 40},
                               "request_pool": 1})
    checked = serve.reference_gaps(
        cell, weights, serve.checked_samples(finished, 1, SEED, tap))
    numbers = {name: value for name, value, _ in serve.compare(
        checked, TINY_LIMITS)}
    assert numbers["router_mismatch"] > TINY_LIMITS["router_mismatch"]


def test_without_reported_routing_nothing_passes(served):
    serve, cell, weights, samples = served
    bare = [{k: v for k, v in s.items() if k != "routing"}
            for s in samples]
    checked = serve.reference_gaps(cell, weights, bare)
    numbers = {name: value for name, value, _ in serve.compare(
        checked, TINY_LIMITS)}
    assert numbers["routing_mismatch"] == float("inf")
    assert numbers["router_mismatch"] == float("inf")
    assert numbers["logit_gap"] <= TINY_LIMITS["logit_gap"]


def test_pool_holds_every_slot_at_its_longest():
    from benchmark import traffic_gen
    traffic = harness.load_json("traffic", "serve-doc32.json")
    prompts = traffic_gen.quantile_lengths(traffic["prompt_len"], 64)
    answers = traffic_gen.quantile_lengths(traffic["answer_len"], 64)
    assert min(prompts) >= 128 and max(prompts) <= 8192
    assert min(answers) >= 32 and max(answers) <= 1024
    assert 2500 < sum(prompts) / 64 < 3100
    assert 260 < sum(answers) / 64 < 320
    assert (traffic["clients"], traffic["max_slots"]) == (32, 32)
    assert traffic["prefill_chunk"] == 512
    pages = -(-(8192 + 1024) // 128)
    assert traffic["kv_pool_pages"] == 32 * pages == 2304
    assert -(-(traffic_gen.longest_request(traffic) + 2) // 128) <= pages
    model = harness.load_cell(CELL)["model"]
    assert traffic_gen.longest_request(traffic) <= model["max_seq_len"]
    assert 5 * 2304 * 128 * work.latent_row_bytes(model) == 1_698_693_120


def test_configuration_keeps_the_published_widths():
    config = harness.load_json("configs", "joyai-llm-flash.json")
    model = config["model"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert (config["num_hidden_layers"], model["n_layers"],
            config["published_num_hidden_layers"]) == (5, 5, 40)
    published = {
        "hidden_size": 2048, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "num_attention_heads": 32, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 256,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "intermediate_size": 7168,
        "vocab_size": 129280, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.5, "rope_theta": 32000000,
        "max_position_embeddings": 131072, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "rope_interleave": True, "tie_word_embeddings": False}
    assert {k: config[k] for k in published} == published
    assert (model["d_model"], model["q_lora_rank"], model["kv_lora_rank"],
            model["n_heads"], model["qk_nope_head_dim"],
            model["qk_rope_head_dim"], model["v_head_dim"],
            model["n_experts"], model["expert_width"],
            model["experts_per_token"], model["n_shared_experts"],
            model["d_ffn"], model["vocab_size"], model["leading_dense"]) == (
        2048, 1536, 512, 32, 128, 64, 128, 256, 768, 8, 1, 7168, 129280, 1)
    for key in ("latent_norms", "selection_bias", "softmax_scale",
                "shared_expert_width", "router_dtype", "prediction_module"):
        assert key in config["assumed"]
    bench = harness.load_cell(CELL)["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert (entry["source"], entry["reduced"]) == (config["source"],
                                                   config["reduced"])


def test_work_counts_against_hand_sums():
    model = harness.load_cell(CELL)["model"]
    # q_a 2048x1536, q_b 1536x6144, kv_a 2048x576, kv_b 512x8192,
    # o 4096x2048
    assert work.attention_params(model) == (
        3145728 + 9437184 + 1179648 + 4194304 + 8388608) == 26_345_472
    assert work.expert_params(model) == 3 * 2048 * 768 == 4_718_592
    # the router 2048x256 and 8 + 1 experts
    assert work.expert_layer_active_params(model) == 524288 + 9 * 4_718_592
    assert work.active_params_per_token(model) == (
        5 * 26_345_472 + 3 * 2048 * 7168 + 4 * (524288 + 9 * 4_718_592))
    assert work.head_params(model) == 129280 * 2048
    assert work.token_flops(model, 10, 3) == (
        2.0 * work.active_params_per_token(model) * 10
        + 2.0 * 129280 * 2048 * 3)
    # a key costs a query 2 x 32 x (2 x 512 + 64) absorbed, 2 x 32 x 320
    # expanded, and 2 x 512 x 32 x 256 to make
    assert work._per_key(model) == (69632, 20480, 8388608)
    assert work.decode_attention_flops(model, [1, 100]) == 5 * 69632 * 101
    keys = sum(range(513, 1025))
    assert work.chunk_attention_flops(model, 512, 512) == 5 * (
        20480 * keys + 8388608 * 1024)
    # three tokens alone: absorbed (6 keys) is the cheaper
    assert work.chunk_attention_flops(model, 0, 3) == 5 * 69632 * 6
    assert work.latent_row_bytes(model) == 1152
    assert work.cache_bytes_per_token(model) == 5760
    v5e = peaks.peaks_for("TPU v5 lite")
    # 160 experts touched by 32 tokens in one layer: bound by bytes
    least = work.expert_layer_least_seconds(model, 32, 160, 1, v5e)
    nbytes = (160 * 9_437_184 + 9_437_184 + 2048 * 256 * 4
              + 32 * 2 * 2048 * 2)
    assert least == pytest.approx(nbytes / v5e["hbm_bytes_per_s"])
    # 100k rows read by 32 slots in one layer
    least = work.attend_least_seconds(model, 1e5, 32, 1, v5e)
    assert least == pytest.approx(
        (1e5 * 1152 + 32 * 32 * 1088 * 2) / v5e["hbm_bytes_per_s"])
    # a decode step at 32 slots, 160 experts a layer: the experts' share
    # of the bytes (ISSUE 35: four fifths)
    experts = 4 * 160 * 9_437_184
    rest = 2 * (work.active_params_per_token(model) - 4 * 8 * 4_718_592
                + work.head_params(model))
    assert 0.75 < experts / (experts + rest + 0.55e9) < 0.85


def _ctx(cell, trace, counters):
    return {"cell": cell, "model": cell["model"], "traffic": cell["traffic"],
            "peaks": peaks.peaks_for("TPU v5 lite"), "trace": trace,
            "counters": counters, "spans": harness.Spans(),
            "trace_reduce": trace_reduce, "notes": []}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_reads_nothing_from_nothing(metric):
    """No trace; and a trace in which none of the new kernels ran, no
    scope was named and the program recorded no span (what the parent
    commit gives): ``None``, never 0."""
    cell = harness.load_cell(CELL)
    reader = harness.load_module("layer_metrics", metric)
    assert reader.read(_ctx(cell, None, {"traced": None})) is None
    empty = {"window_s": 1.0, "busy_s": 0.5, "idle_share": 0.5,
             "op_self_s": {"fusion.1": 0.5}, "op_calls": {"fusion.1": 3},
             "gaps": [], "n_devices": 1}
    counters = {"traced": (0.0, 1.0), "tokens": [], "prefills": [],
                "steps": [], "scope_s": None}
    assert reader.read(_ctx(cell, empty, counters)) is None


def test_the_cell_is_listed_where_the_issue_says():
    bench = harness.load_cell(CELL)["bench"]
    assert len(bench["workloads"]) == 5 and len(bench["configs"]) == 3
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-llm-flash", "serve-doc32", 1)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        "ttft_p95.serve", "gap_p95.serve", "engine_step_p50.serve",
        "slot_occupancy.serve", "kv_pool_fill.serve", "kv_pages_used.serve",
        "device_idle.serve", "host_work_p50.serve", "admit_host_p50.serve",
        "queue_wait_p50.serve", *NEW_METRICS}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
    e2e = {m["name"] for m in harness.metrics_of(harness.load_cell(CELL),
                                                 "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "serve_ttft_p50_ms", "setup_s"}


def _xspace(ops, tf_ops, spans):
    """A hand-made trace as the chip's profiler writes it: operations
    ``(hlo text, start ns, ns)`` whose metadata names the scopes
    (``tf_ops``: by hlo text), host spans ``(name, start ns, ns)``."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    for name, line_name, events in (
            ("/host:CPU", "python", spans),
            ("/device:TPU:0", "XLA Ops", ops),
            ("/device:TPU:0 SparseCore", "XLA Ops", ops)):
        plane = space.planes.add(name=name)
        plane.stat_metadata[7].name = "tf_op"
        line = plane.lines.add(name=line_name, timestamp_ns=1000)
        ids = {}
        for text, start, dur in events:
            if text not in ids:
                ids[text] = len(ids) + 1
                meta = plane.event_metadata[ids[text]]
                meta.name = text
                if text in tf_ops:
                    meta.stats.add(metadata_id=7, str_value=tf_ops[text])
            line.events.add(metadata_id=ids[text],
                            offset_ps=(start - 1000) * 1000,
                            duration_ps=dur * 1000)
    return space


def test_read_trace_gives_the_neutral_form_and_seconds_by_scope(tmp_path):
    """One pass over the raw trace: what ``trace_reduce`` reduces, and
    self times under the scope each operation's ``tf_op`` names (a
    ``while`` is charged what its children leave; only the window)."""
    pytest.importorskip("tensorflow")
    from benchmark.kinds import serve_latent_moe as serve
    ops = [("%while.1 = while()", 1000, 1000),
           ("%fusion.2 = fusion()", 1000, 400),
           ("%moe_experts.3 = custom-call()", 1400, 500),
           ("%fusion.9 = fusion()", 2000, 300)]
    tf_ops = {"%while.1 = while()": "jit(step)/while:",
              "%fusion.2 = fusion()":
                  "jit(step)/while/body/moe_route/dot_general:",
              "%moe_experts.3 = custom-call()":
                  "jit(step)/while/body/moe_experts/moe_experts:",
              "%fusion.9 = fusion()": "jit(chunk)/mla_prefill/exp:"}
    spans = [("bench/traced_window", 1100, 1100), ("serve/step", 1100, 50),
             ("$profiler.py:101 start_trace", 1000, 10)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, tf_ops, spans).SerializeToString())
    trace, scope_s = serve.read_trace(str(path))
    assert set(trace) == {"/host:CPU", "/device:TPU:0"}
    assert trace["/host:CPU"]["python"] == [
        ("bench/traced_window", 1100.0, 1100.0), ("serve/step", 1100.0, 50.0)]
    assert trace["/device:TPU:0"]["XLA Ops"] == [
        (text, float(start), float(dur)) for text, start, dur in ops]
    assert scope_s == pytest.approx({
        "moe_route": 300e-9, "moe_experts": 500e-9, "mla_prefill": 200e-9})
    reduced = trace_reduce.reduce_trace(trace)
    assert reduced["busy_s"] == pytest.approx(1100e-9)
    # a trace that names no scope (the parent's): nothing, not zeros
    path.write_bytes(_xspace(ops, {}, spans).SerializeToString())
    assert serve.read_trace(str(path))[1] is None


def test_read_trace_agrees_with_load_xplane_on_a_real_trace(tmp_path):
    """On a trace the profiler wrote here (host spans only): the same
    names at the same times as ``trace_reduce.load_xplane``."""
    pytest.importorskip("tensorflow")
    from benchmark.kinds import serve_latent_moe as serve
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/traced_window"):
        with jax.profiler.TraceAnnotation("serve/step", tokens=3):
            jax.numpy.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    ours, scope_s = serve.read_trace(path)
    theirs = trace_reduce.load_xplane(path)
    assert scope_s is None
    flat = lambda t: sorted(ev for lines in t.get("/host:CPU", {}).values()
                            for ev in lines)
    assert [n for n, _, _ in flat(ours)] == [n for n, _, _ in flat(theirs)]
    assert len(flat(ours)) == 2
    for (_, s0, d0), (_, s1, d1) in zip(flat(ours), flat(theirs)):
        assert s0 == pytest.approx(s1, abs=1.0) and d0 == pytest.approx(
            d1, abs=1.0)
