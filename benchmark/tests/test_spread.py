"""``spread.py``: the measure, the command's reading of a result line, and
the rule that BENCHMARK.json's bounds have to follow from the sets kept in
``spreads.json``. No chip: the command's runs are canned."""

import io
import json
import subprocess

import pytest

from benchmark import harness, spread as spread_lib

PARENT_BOUNDS = {                # BENCHMARK.json before PR 32 (PR 24's)
    "train_tokens_per_s": 0.01, "serve_tokens_per_s": 0.01,
    "serve_ttft_p50_ms": 0.01, "setup_s": 0.1,
}


@pytest.mark.parametrize("values, expected", [
    # PR 31's kind of side: five runs together and one far off
    ([13.52, 13.50, 13.55, 13.49, 13.53, 13.95], 13.55 - 13.49),
    # the far run on the low side
    ([2060.0, 2071.0, 2065.0, 2068.0, 1990.0, 2063.0], 2071.0 - 2060.0),
    # leaving a run out narrows nothing: both ends are held twice
    ([1.0, 1.0, 2.0, 3.0, 3.0], 2.0),
    # fewer than three values: nothing is left out
    ([5.0, 7.0], 2.0),
    ([5.0], 0.0),
])
def test_spread(values, expected):
    assert spread_lib.spread(values) == pytest.approx(expected)


def test_spread_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        spread_lib.spread([])


def test_quartile_spread_is_the_statistics_modules():
    # six values, the exclusive method: q1 = 1.75th, q3 = 5.25th value
    assert spread_lib.quartile_spread([1, 2, 3, 4, 5, 6]) \
        == pytest.approx(5.25 - 1.75)


@pytest.mark.parametrize("values, expected", [
    # the far run goes, then the quartiles of the five that are left
    # (exclusive method: the 1.5th and the 4.5th value)
    ([13.52, 13.50, 13.55, 13.49, 13.53, 13.95], 13.54 - 13.495),
    # leaving an end out narrows nothing: the quartiles of all six
    ([1.0, 2.0, 2.0, 2.0, 2.0, 3.0], 0.5),
    # never wider than spread()
    ([2060.0, 2071.0, 2065.0, 2068.0, 1990.0, 2063.0], 2069.5 - 2061.5),
    ([5.0, 7.0], 3.0),           # two values: statistics' own quartiles
    ([5.0], 0.0),
])
def test_middle_half(values, expected):
    assert spread_lib.middle_half(values) == pytest.approx(expected)
    if len(values) >= 3:
        assert spread_lib.middle_half(values) <= spread_lib.spread(values)


@pytest.mark.parametrize("widest, parent, expected", [
    (0.0030, 0.01, 0.01),        # far under: the bound stays
    (0.0050, 0.01, 0.01),        # exactly half of 1%
    (0.0068, 0.01, 0.015),
    (0.0170, 0.01, 0.04),        # the ledger's widest TTFT side (PR 31)
    (0.0260, 0.01, 0.06),        # past 5%: the steps PR 32's check forced
    (0.0371, 0.01, 0.075),       # that check's wider set of TTFT medians
    (0.0560, 0.01, 0.10),        # past the ladder: its top step, the
                                 # largest bound the contract allows
    (0.0030, 0.02, 0.02),        # never lowered
    (0.0900, 0.10, 0.10),        # setup_s keeps 10%
])
def test_bound_for(widest, parent, expected):
    assert spread_lib.LADDER[-1] == 0.1
    assert spread_lib.bound_for(widest, parent) == expected


CANNED = (
    '[bench] serve: window 30.004 s: 61815 tokens delivered\n'
    '{"correct": true, "attempted": 1093, "failed": 0, "metrics": '
    '{"setup_s": {"value": 27.74, "unit": "s"}, "serve_tokens_per_s": '
    '{"value": 2060.1, "unit": "tokens/s"}, "serve_ttft_p50_ms": '
    '{"value": 13.609, "unit": "ms"}}, "device": {"platform": "tpu", '
    '"kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 9900000000}, '
    '"compared": {"logit_gap": {"value": 0.03, "limit": 0.09}}}\n')


def test_parse_result_line():
    parsed = spread_lib.parse_result_line(CANNED)
    assert parsed == {"correct": True, "values": {
        "setup_s": 27.74, "serve_tokens_per_s": 2060.1,
        "serve_ttft_p50_ms": 13.609}}
    assert spread_lib.parse_result_line(
        CANNED.replace('"correct": true', '"correct": false'))["correct"] \
        is False


@pytest.mark.parametrize("stdout", [
    "", "[bench] jax 0.9.0\nbench: needs a TPU\n", '{"correct": true}\n'])
def test_no_result_line_is_no_result(stdout):
    assert spread_lib.parse_result_line(stdout) is None


def test_a_run_that_is_not_correct_is_shown_and_counted(monkeypatch):
    """The command over canned children: every run in the report, the one
    that is not correct named, and a child that printed nothing kept as a
    run with no values."""
    outs = iter([CANNED,
                 CANNED.replace('"correct": true', '"correct": false')
                       .replace("13.609", "13.9"),
                 "bench: needs a TPU\n"])

    def fake_run(cmd, **kw):
        assert "--trace" in cmd and cmd[cmd.index("--trace") + 1] == "0"
        return subprocess.CompletedProcess(cmd, 0, stdout=next(outs))

    monkeypatch.setattr(spread_lib.subprocess, "run", fake_run)
    runs = spread_lib.run_set("gpt3-xl.serve-chat16", 3, 30, 2**31 + 5)
    assert [r["seed"] for r in runs] == [2**31 + 5, 2**31 + 6, 2**31 + 7]
    assert [r["correct"] for r in runs] == [True, False, False]
    assert runs[2]["values"] == {}
    out = io.StringIO()
    spread_lib.report(runs, {"serve_ttft_p50_ms": 0.01}, {}, out=out)
    text = out.getvalue()
    assert "serve_ttft_p50_ms: runs 13.609 13.9;" in text
    assert "OVER HALF" in text
    assert f"3 runs, 2 not correct: seeds [{2**31 + 6}, {2**31 + 7}]" in text


def _spreads():
    with open(spread_lib.SPREADS) as f:
        return json.load(f)


def test_every_kept_set_is_whole():
    bench = harness.load_cell("gpt3-xl.serve-chat16")["bench"]
    cells = {w["name"] for w in bench["workloads"]}
    for entry in _spreads()["sets"]:
        assert entry["cell"] in cells
        assert entry["from"] in ("chip", "ledger", "check")
        if entry["from"] != "chip":
            assert entry["spreads"] and "pr" in entry and "side" in entry
            continue
        assert entry["commit"] and entry["name"] and len(entry["runs"]) >= 3
        seeds = [r["seed"] for r in entry["runs"]]
        assert len(set(seeds)) == len(seeds)
        for run in entry["runs"]:
            assert run["correct"] is True, (entry["name"], run["seed"])
            assert "setup_s" in run["values"] and len(run["values"]) >= 2


def test_benchmark_json_follows_the_rule():
    """For every bounded metric: the bound is at least twice the widest
    spread on record and the smallest step of the ladder that is, or it is
    the bound the parent had (never lowered); the ledger's 1.70% is among
    what the TTFT bound was set from, and so is the wider set of PR 32's
    first check (3.71%); a set that ran in an experiment's environment is
    not."""
    bench = harness.load_cell("gpt3-xl.serve-chat16")["bench"]
    widest = spread_lib.widest_shares(_spreads()["sets"],
                                      bench["run_seconds"])
    assert widest["serve_ttft_p50_ms"][0] >= 0.51137 / 13.7981 \
        > 0.230142 / 13.5207
    experiments = [e for e in _spreads()["sets"] if "experiment" in e]
    assert experiments and max(
        spread_lib.set_shares(e)["serve_ttft_p50_ms"] for e in experiments) \
        > widest["serve_ttft_p50_ms"][0]
    for metric in bench["end_to_end"]:
        name, bound = metric["name"], metric["bound"]
        parent = PARENT_BOUNDS[name]
        assert bound >= parent
        if name not in widest or name == "setup_s":
            assert bound == parent
            continue
        share = widest[name][0]
        assert bound == spread_lib.bound_for(share, parent), (name, share)
        assert bound >= 2 * share or bound == spread_lib.LADDER[-1]
        lower = [s for s in spread_lib.LADDER if parent <= s < bound]
        assert all(2 * share > s for s in lower)


def test_the_rule_prints(capsys):
    assert spread_lib.main(["--rule"]) == 0
    out = capsys.readouterr().out
    assert "serve_ttft_p50_ms: widest spread" in out
