"""bench_diff regression sentinel (ISSUE 15): direction-aware
row-by-row comparison of BENCH snapshots — improvements pass,
regressions fail by name, vanished rows fail (the r05
RESOURCE_EXHAUSTED signature), schema mismatches refuse to compare,
and the checked-in synthetic snapshot self-diffs clean.
"""

import copy
import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "bench_diff", os.path.join(REPO, "tools", "bench_diff.py"))
bd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bd)


def _doc(**extra):
    base = {"decode_engine_tokens_per_sec": 1000.0,
            "decode_engine_paged_tokens_per_sec": 400.0,
            "step_ms": 50.0,
            "decode_batch": 8}
    base.update(extra)
    return {"metric": "gpt_tokens_per_sec", "value": 100.0,
            "unit": "tokens/s", "vs_baseline": 1.0, "extra": base}


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


# -- verdict classes ----------------------------------------------------------

def test_within_noise_is_clean():
    new = _doc(decode_engine_tokens_per_sec=1030.0, step_ms=51.0)
    v = bd.compare(_doc(), new)
    assert v["regressions"] == [] and v["improvements"] == []
    assert any(r == "decode_engine_tokens_per_sec"
               for r, _ in v["within_noise"])


def test_tok_s_regression_named():
    new = _doc(decode_engine_tokens_per_sec=800.0)   # -20%
    v = bd.compare(_doc(), new)
    rows = [r for r, _ in v["regressions"]]
    assert rows == ["decode_engine_tokens_per_sec"]


def test_tok_s_improvement_passes():
    v = bd.compare(_doc(), _doc(decode_engine_tokens_per_sec=1300.0))
    assert v["regressions"] == []
    assert any(r == "decode_engine_tokens_per_sec"
               for r, _ in v["improvements"])


def test_ms_direction_inverted():
    assert [r for r, _ in
            bd.compare(_doc(), _doc(step_ms=70.0))["regressions"]] \
        == ["step_ms"]
    assert [r for r, _ in
            bd.compare(_doc(), _doc(step_ms=30.0))["improvements"]] \
        == ["step_ms"]


def test_missing_numeric_row_is_regression():
    new = _doc()
    del new["extra"]["decode_engine_tokens_per_sec"]
    v = bd.compare(_doc(), new)
    assert any(r == "decode_engine_tokens_per_sec"
               and "vanished" in d for r, d in v["regressions"])


def test_row_died_with_error_marker_is_regression():
    """A SECTION marker (decode_engine_error) must be attributed to the
    longer-named rows it killed — the exact r05 signature."""
    new = _doc()
    del new["extra"]["decode_engine_tokens_per_sec"]
    new["extra"]["decode_engine_error"] = "RESOURCE_EXHAUSTED: boom"
    v = bd.compare(_doc(), new)
    hits = [(r, d) for r, d in v["regressions"]
            if r == "decode_engine_tokens_per_sec"]
    assert hits and "row died" in hits[0][1] \
        and "RESOURCE_EXHAUSTED" in hits[0][1]


def test_zero_baseline_micro_drift_within_noise():
    """An exactly-0.0 baseline row (overlap's pinned exposed_s) that
    drifts by micro-units must not read as an infinite regression —
    but a real regrowth past atol still fails."""
    v = bd.compare(_doc(train_overlap_exposed_s=0.0),
                   _doc(train_overlap_exposed_s=1e-7))
    assert v["regressions"] == []
    v = bd.compare(_doc(train_overlap_exposed_s=0.0),
                   _doc(train_overlap_exposed_s=0.002))
    assert any(r == "train_overlap_exposed_s"
               for r, _ in v["regressions"])


def test_missing_informational_row_not_regression():
    new = _doc()
    del new["extra"]["decode_batch"]
    v = bd.compare(_doc(), new)
    assert v["regressions"] == []
    assert any(r == "decode_batch" for r, _ in v["missing"])


def test_new_rows_reported_never_failed():
    v = bd.compare(_doc(), _doc(brand_new_tokens_per_sec=10.0))
    assert v["regressions"] == []
    assert any(r == "brand_new_tokens_per_sec" for r, _ in v["added"])


def test_goodput_dip_is_lower_is_better():
    """The drain bench's goodput_dip_frac row embeds the "goodput"
    fragment but measures a COST — a bigger dip must regress, a
    smaller one improve (ISSUE 16 direction tagging)."""
    assert bd.direction("fleet_churn_drain_goodput_dip_frac") == -1
    v = bd.compare(_doc(fleet_churn_drain_goodput_dip_frac=0.10),
                   _doc(fleet_churn_drain_goodput_dip_frac=0.40))
    assert any(r == "fleet_churn_drain_goodput_dip_frac"
               for r, _ in v["regressions"])
    v = bd.compare(_doc(fleet_churn_drain_goodput_dip_frac=0.40),
                   _doc(fleet_churn_drain_goodput_dip_frac=0.10))
    assert v["regressions"] == []
    # ...while plain goodput rows keep their higher-is-better sense
    assert bd.direction("fleet_churn_drain_goodput_tokens_per_sec") == 1
    # fault-path counters introduced by the live-reshard/drain paths
    assert bd.direction("fleet_reshard_fallbacks") == -1
    assert bd.direction("serve_drain_migrate_failed") == -1


def test_launch_rows_are_lower_is_better():
    """The kernel-launch accounting rows (ISSUE 19): MORE launches
    per token/step is a regression, fewer is the win. The row name
    must not be swallowed by the higher-is-better token fragments."""
    assert bd.direction("decode_engine_paged_launches_per_token") == -1
    assert bd.direction("decode_spec_paged_launches_per_step") == -1
    v = bd.compare(_doc(decode_engine_paged_launches_per_step=2.0),
                   _doc(decode_engine_paged_launches_per_step=24.0))
    assert any(r == "decode_engine_paged_launches_per_step"
               for r, _ in v["regressions"])
    v = bd.compare(_doc(decode_engine_paged_launches_per_step=24.0),
                   _doc(decode_engine_paged_launches_per_step=2.0))
    assert v["regressions"] == []


def test_spec_paged_row_death_guarded_by_name():
    """The revived paged-spec bench row must die LOUDLY: a vanished
    decode_spec_paged_* row with its section error marker (the r05
    RESOURCE_EXHAUSTED signature) is a named regression, never a
    silent drop."""
    base = _doc(decode_spec_paged_tokens_per_sec=900.0)
    new = _doc()
    new["extra"]["decode_spec_paged_error"] = "RESOURCE_EXHAUSTED: oom"
    v = bd.compare(base, new)
    hits = [(r, d) for r, d in v["regressions"]
            if r == "decode_spec_paged_tokens_per_sec"]
    assert hits and "RESOURCE_EXHAUSTED" in hits[0][1]


def test_failover_rows_direction_tagged():
    """The router-failover bench rows (ISSUE 17): recovery time is a
    cost, republished-result counts are informational (they scale
    with where the kill lands, not with quality), and the failover
    goodput/dip rows inherit the drain phase's tagging."""
    assert bd.direction("fleet_churn_failover_recovery_s") == -1
    v = bd.compare(_doc(fleet_churn_failover_recovery_s=0.01),
                   _doc(fleet_churn_failover_recovery_s=0.50))
    assert any(r == "fleet_churn_failover_recovery_s"
               for r, _ in v["regressions"])
    v = bd.compare(_doc(fleet_churn_failover_recovery_s=0.50),
                   _doc(fleet_churn_failover_recovery_s=0.01))
    assert v["regressions"] == []
    assert bd.direction("fleet_churn_failover_republished") == 0
    v = bd.compare(_doc(fleet_churn_failover_republished=6),
                   _doc(fleet_churn_failover_republished=0))
    assert v["regressions"] == []
    assert bd.direction(
        "fleet_churn_failover_goodput_tokens_per_sec") == 1
    assert bd.direction("fleet_churn_failover_goodput_dip_frac") == -1


def test_noise_table_widens_p99():
    # 20% swing on a p99 row sits inside the 25% noise band...
    v = bd.compare(_doc(serve_p99_ttft_ms=100.0),
                   _doc(serve_p99_ttft_ms=120.0))
    assert v["regressions"] == []
    # ...but a 40% swing does not
    v = bd.compare(_doc(serve_p99_ttft_ms=100.0),
                   _doc(serve_p99_ttft_ms=140.0))
    assert any(r == "serve_p99_ttft_ms" for r, _ in v["regressions"])


# -- schema / CLI -------------------------------------------------------------

def test_schema_mismatch_exits_2(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _doc())
    other = _doc()
    other["metric"] = "bert_tokens_per_sec"
    b = _write(tmp_path, "b.json", other)
    assert bd.main([a, b]) == 2
    assert "not comparable" in capsys.readouterr().err


def test_provenance_schema_version_mismatch_exits_2(tmp_path):
    da, db = _doc(), _doc()
    da["provenance"] = {"schema_version": 1}
    db["provenance"] = {"schema_version": 2}
    a = _write(tmp_path, "a.json", da)
    b = _write(tmp_path, "b.json", db)
    assert bd.main([a, b]) == 2


def test_cli_regression_exit_1_names_row(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _doc())
    b = _write(tmp_path, "b.json",
               _doc(decode_engine_tokens_per_sec=800.0))
    assert bd.main([a, b]) == 1
    assert "decode_engine_tokens_per_sec" in capsys.readouterr().out


def test_cli_unreadable_input_exits_2(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    assert bd.main([str(p), str(p)]) == 2
    q = tmp_path / "shape.json"
    q.write_text(json.dumps({"rows": []}))
    assert bd.main([str(q), str(q)]) == 2


def test_driver_wrapper_shape_accepted(tmp_path):
    wrapped = {"n": 5, "cmd": "python bench.py", "rc": 0, "tail": "",
               "parsed": _doc()}
    a = _write(tmp_path, "a.json", wrapped)
    b = _write(tmp_path, "b.json", _doc())
    assert bd.main([a, b]) == 0


def test_checked_in_snapshot_self_diff_clean(capsys):
    """The tracked SYNTHETIC snapshot tools/ci.sh self-diffs (no chip
    record lives in the tree: the driver's ledger holds those). It is
    `_doc()` in the driver's wrapper shape — asserted, so the fixture
    and the test's own rows cannot drift apart."""
    path = os.path.join(REPO, "tests", "fixtures", "bench_snapshot.json")
    with open(path) as f:
        assert json.load(f)["parsed"] == _doc()
    assert bd.main([path, path]) == 0
    assert "clean" in capsys.readouterr().out


def test_selftest_catches_synthetic_regression(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _doc())
    assert bd.main(["--selftest", a]) == 0
    assert "caught" in capsys.readouterr().out
    # and the harness itself: a maimed copy really exits 1
    wounded = copy.deepcopy(_doc())
    wounded["extra"]["decode_engine_tokens_per_sec"] *= 0.8
    b = _write(tmp_path, "b.json", wounded)
    assert bd.main([a, b]) == 1


def test_paged_flip_report():
    lines = bd.paged_flip_report(_doc())   # 1000/400 = 2.5x
    assert lines and "2.50x" in lines[0] and "not yet" in lines[0]
    ok = bd.paged_flip_report(
        _doc(decode_engine_paged_tokens_per_sec=900.0))
    assert ok and "PASS" in ok[0]
    assert bd.paged_flip_report({"extra": {}}) == []
