"""The runner end to end at a tiny size on the CPU: it refuses to print a
result without a TPU; past that look, a sound run is ``correct`` and a run
with the timed path broken underneath is not — once for each fault a cell
of these kinds can have (a step that returns its state unchanged; half of
the batch left out, the mean taken over the rest; a token altered where it
is produced). The exchange between chips has no cell here.

The limits are the real cells' (``limits/<cell>.json``): the tiny model is
the same code in the same precisions, and reads under them.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark import harness, run as runner

ROOT = harness.ROOT
SEED = 2**31 + 2024            # the driver's seeds pass 31 bits


def _execute(cell, capsys, trace=False, seconds=1.5):
    result = runner.execute(cell, SEED, seconds, trace, jax,
                            jax.devices()[:1])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    return result, line, out.err


def test_no_tpu_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt3-xl.train-2k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_every_cell_finds_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        harness.load_module("kinds", cell["traffic"]["kind"])
        assert {m["name"] for m in harness.metrics_of(cell, "end_to_end")} \
            >= {"setup_s"}
        for metric in harness.metrics_of(cell, "per_layer"):
            assert hasattr(harness.load_module("layer_metrics",
                                               metric["name"]), "read")


def test_parked_cell_files_load():
    """``gpt3-medium.train-2k-b8`` is left out of BENCHMARK.json (PERF.md
    section 7); its files have to stay loadable for the PR that adds it."""
    config = harness.load_json("configs", "gpt3-medium.json")
    job = harness.load_json("traffic", "train-2k-b8.json")
    limits = harness.load_json("limits", "gpt3-medium.train-2k-b8.json")
    harness.load_module("kinds", job["kind"])
    assert job["seq_len"] <= config["model"]["max_seq_len"] == 2048
    assert config["model"]["n_heads"] * config["model"]["head_dim"] \
        == config["model"]["d_model"]
    assert {k for k in limits if not k.startswith("_")} \
        == {"loss_gap_step1", "grad_norm_gap", "change_norm_gap"}


def test_train_run_is_correct(tiny_train_cell, capsys):
    result, line, err = _execute(tiny_train_cell, capsys)
    assert result["correct"] and line["correct"] is True
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == set(
        k for k in tiny_train_cell["limits"] if not k.startswith("_"))
    assert "compared grad_norm_gap" in err.strip().splitlines()[-2]
    assert line["device"]["platform"] == "cpu"      # named, never assumed


def _break_train_step(monkeypatch, fault):
    from paddle_tpu.models import gpt
    real = gpt.build_train_step

    def build(model, optimizer, mesh=None, donate=True):
        step = real(model, optimizer, mesh, donate=False)
        if fault == "state_unchanged":
            return lambda p, s, t, r: (p, s, step(p, s, t, r)[2])
        if fault == "half_batch":
            return lambda p, s, t, r: step(p, s, t[:t.shape[0] // 2], r)
        raise AssertionError(fault)

    monkeypatch.setattr(gpt, "build_train_step", build)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(tiny_train_cell, capsys, monkeypatch,
                                    fault):
    _break_train_step(monkeypatch, fault)
    result, line, _ = _execute(tiny_train_cell, capsys)
    assert line["correct"] is False
    over = [k for k, v in line["compared"].items() if v["value"] > v["limit"]]
    assert "grad_norm_gap" in over
    if fault == "state_unchanged":
        # nothing moved: both norms' gaps read 1 by the measure
        assert line["compared"]["grad_norm_gap"]["value"] \
            == pytest.approx(1.0)
        assert line["compared"]["change_norm_gap"]["value"] \
            == pytest.approx(1.0)


def test_serve_run_is_correct(tiny_serve_cell, capsys):
    result, line, _ = _execute(tiny_serve_cell, capsys, seconds=2.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "serve_ttft_p50_ms", "setup_s"}
    assert len(result["counters"]["gap_ms"]) > 0
    assert result["counters"]["compiled_in_window"] == 0


def test_serve_altered_token_is_not_correct(tiny_serve_cell, capsys,
                                            monkeypatch):
    from paddle_tpu.serving import scheduler
    real = scheduler.FrontEnd._on_token
    vocab = tiny_serve_cell["model"]["vocab_size"]

    def altered(self, ereq, token):
        # the third token of every request, altered where it is produced
        if len(ereq.tokens) == 3:
            token = (token + vocab // 2) % vocab
        return real(self, ereq, token)

    monkeypatch.setattr(scheduler.FrontEnd, "_on_token", altered)
    _, line, _ = _execute(tiny_serve_cell, capsys, seconds=2.0)
    assert line["correct"] is False
    assert line["compared"]["logit_gap"]["value"] \
        > line["compared"]["logit_gap"]["limit"]
