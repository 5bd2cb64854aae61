"""The retention configuration's cell at a tiny size on the CPU: the new
kind end to end (sound, and with a token altered), the decode-heavy mix
through the kind that exists, the new counts of work against hand sums,
and every new per-layer metric silent where there is nothing to read."""

import copy
import json

import jax
import pytest

from benchmark import harness, peaks, run as runner, trace_reduce
from benchmark import work_retention as work

SEED = 2**31 + 2029
TINY = {
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 16, "d_ffn": 96, "vocab_size": 256, "max_seq_len": 512,
    "rope_theta": 1000000, "norm_eps": 1e-06, "use_bias": False,
    "tie_embeddings": False, "dtype": "bfloat16",
}
NEW_METRICS = ("step_mfu.serve_retention", "retention_step_roofline.serve",
               "retention_time_share.serve")


@pytest.fixture
def tiny_retention_cell():
    cell = copy.deepcopy(harness.load_cell("brumby-14b.serve-longgen16"))
    cell["model"] = dict(TINY)
    cell["traffic"].update(
        clients=3, max_slots=3, request_pool=6, checked_requests=3,
        prefill_chunk=16,
        prompt_len={"dist": "lognormal", "median": 24, "sigma": 1.0,
                    "min": 4, "max": 100},
        answer_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                    "min": 4, "max": 16})
    return cell


def _execute(cell, capsys, seconds=2.0):
    result = runner.execute(cell, SEED, seconds, False, jax,
                            jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return result, line


def test_retention_cell_is_correct(tiny_retention_cell, capsys):
    result, line = _execute(tiny_retention_cell, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "serve_ttft_p50_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_gap", "unfinished"}
    assert result["counters"]["compiled_in_window"] == 0
    longest = max(n for _, n in result["counters"]["prefills"])
    assert longest > 3 * tiny_retention_cell["traffic"]["prefill_chunk"]


def test_retention_altered_token_is_not_correct(tiny_retention_cell, capsys,
                                                monkeypatch):
    from paddle_tpu.serving import scheduler
    real = scheduler.FrontEnd._on_token
    vocab = TINY["vocab_size"]

    def altered(self, ereq, token):
        if len(ereq.tokens) == 3:
            token = (token + vocab // 2) % vocab
        return real(self, ereq, token)

    monkeypatch.setattr(scheduler.FrontEnd, "_on_token", altered)
    _, line = _execute(tiny_retention_cell, capsys)
    assert line["correct"] is False
    assert line["compared"]["logit_gap"]["value"] \
        > line["compared"]["logit_gap"]["limit"]


def test_decode_heavy_cell_is_correct(capsys):
    from benchmark.tests.conftest import TINY_MODEL
    cell = copy.deepcopy(harness.load_cell("gpt3-xl.serve-decode-heavy"))
    assert cell["traffic"]["kind"] == "serve_closed"
    cell["model"] = dict(TINY_MODEL)
    cell["traffic"].update(
        clients=3, max_slots=3, request_pool=6, kv_pool_pages=3,
        checked_requests=3,
        prompt_len={"dist": "uniform", "min": 16, "max": 48},
        answer_len={"dist": "uniform", "min": 12, "max": 24})
    _, line = _execute(cell, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_decode_heavy_pool_holds_every_slot_at_its_longest():
    from benchmark import traffic_gen
    traffic = harness.load_json("traffic", "serve-decode-heavy.json")
    prompts = traffic_gen.quantile_lengths(traffic["prompt_len"], 64)
    answers = traffic_gen.quantile_lengths(traffic["answer_len"], 64)
    assert (min(prompts), max(prompts)) == (16, 64)
    assert (min(answers), max(answers)) == (516, 1020)
    pages = -(-(traffic_gen.longest_request(traffic) + 2) // 128)
    assert traffic["max_slots"] * pages == traffic["kv_pool_pages"] == 144


def test_longgen_lengths_are_the_stated_ones():
    from benchmark import traffic_gen
    traffic = harness.load_json("traffic", "serve-longgen16.json")
    prompts = traffic_gen.quantile_lengths(traffic["prompt_len"], 64)
    answers = traffic_gen.quantile_lengths(traffic["answer_len"], 64)
    assert 11000 < max(prompts) < 12000 and min(prompts) >= 64
    assert 1600 < sum(prompts) / 64 < 1800
    assert 550 < sum(answers) / 64 < 600 and max(answers) < 2048
    model = harness.load_cell("brumby-14b.serve-longgen16")["model"]
    assert traffic_gen.longest_request(traffic) <= model["max_seq_len"]


def test_configuration_keeps_the_published_widths():
    config = harness.load_json("configs", "brumby-14b.json")
    model = config["model"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert (config["num_hidden_layers"], model["n_layers"]) == (8, 8)
    assert (model["d_model"], model["n_heads"], model["n_kv_heads"],
            model["head_dim"], model["d_ffn"], model["vocab_size"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["intermediate_size"], config["vocab_size"]) == (
        5120, 40, 8, 128, 17408, 151936)
    for key in ("power", "gate", "qk_norm", "normaliser", "state_dtype",
                "phi_layout"):
        assert key in config["assumed"]


def test_work_counts_against_hand_sums():
    model = harness.load_cell("brumby-14b.serve-longgen16")["model"]
    assert work.phi_entries(model) == 8256
    # q 5120x5120, k and v 5120x1024 each, gate 5120x8, o 5120x5120,
    # feed-forward 3 x 5120x17408
    assert work.matrix_params_per_layer(model) == (
        26214400 + 2 * 5242880 + 40960 + 26214400 + 3 * 89128960)
    assert work.matrix_params_per_layer(model) == 330_342_400
    assert work.head_params(model) == 777_912_320
    # (8 + 40) heads x 2 x 8256 x 129
    assert work.state_form_flops_per_token(model) == 48 * 2 * 8256 * 129
    # attention form while 4 x 128 x 40 x c is the less: c < 4,992.3
    per_key = 4 * 128 * 40
    assert work.retention_flops(model, [1, 100]) == 8 * per_key * 101
    assert work.retention_flops(model, [4992, 4993, 20000]) == 8 * (
        per_key * 4992 + 2 * 48 * 2 * 8256 * 129)
    assert work.token_flops(model, 10, 3) == (
        2.0 * 8 * 330_342_400 * 10 + 2.0 * 777_912_320 * 3)
    # S and z of 8 heads, read and written in float32; q and o of 40
    # heads, k and v of 8, in bfloat16
    assert work.step_bytes_per_slot(model) == (
        2 * 8 * 8256 * 129 * 4 + (80 + 16) * 128 * 2)
    assert work.state_bytes_per_slot(model) == 8 * 8 * 8256 * 129 * 4
    # a decode step at 16 slots: state against weights (ISSUE 29: 56%)
    state = 16 * 8 * work.step_bytes_per_slot(model)
    weights = 2 * (8 * work.matrix_params_per_layer(model)
                   + work.head_params(model))
    assert 0.55 < state / (state + weights) < 0.57


def _ctx(cell, trace, counters):
    return {"cell": cell, "model": cell["model"], "traffic": cell["traffic"],
            "peaks": peaks.peaks_for("TPU v5 lite"), "trace": trace,
            "counters": counters, "spans": harness.Spans(),
            "trace_reduce": trace_reduce, "notes": []}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_reads_nothing_from_nothing(metric):
    """No trace; and a trace in which no retention kernel ran and the
    program recorded no span (what the parent commit gives): ``None``,
    never 0."""
    cell = harness.load_cell("brumby-14b.serve-longgen16")
    reader = harness.load_module("layer_metrics", metric)
    assert reader.read(_ctx(cell, None, {"traced": None})) is None
    empty = {"window_s": 1.0, "busy_s": 0.5, "idle_share": 0.5,
             "op_self_s": {"fusion.1": 0.5}, "op_calls": {"fusion.1": 3},
             "gaps": [], "n_devices": 1}
    counters = {"traced": (0.0, 1.0), "tokens": [], "prefills": [],
                "steps": []}
    assert reader.read(_ctx(cell, empty, counters)) is None


def test_new_cells_are_listed_where_the_issue_says():
    bench = harness.load_cell("gpt3-xl.serve-chat16")["bench"]
    cells = {w["name"]: w for w in bench["workloads"]}
    new, heavy = "brumby-14b.serve-longgen16", "gpt3-xl.serve-decode-heavy"
    assert cells[new]["chips"] == cells[heavy]["chips"] == 1
    listed = lambda name: {m["name"] for m in bench["per_layer"]
                           if name in m.get("workloads", ())}
    assert listed(new) == {
        "ttft_p95.serve", "gap_p95.serve", "engine_step_p50.serve",
        "slot_occupancy.serve", "device_idle.serve", "host_work_p50.serve",
        "admit_host_p50.serve", "queue_wait_p50.serve", *NEW_METRICS}
    assert listed(heavy) == listed("gpt3-xl.serve-chat16") - {
        "ttft_p95.serve", "admit_host_p50.serve", "queue_wait_p50.serve"}
    e2e = lambda name: {m["name"] for m in harness.metrics_of(
        harness.load_cell(name), "end_to_end")}
    assert e2e(new) == {"serve_tokens_per_s", "serve_ttft_p50_ms", "setup_s"}
    assert e2e(heavy) == {"serve_tokens_per_s", "setup_s"}
