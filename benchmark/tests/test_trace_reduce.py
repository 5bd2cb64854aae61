"""The trace reduction on the small recorded trace: idle share, time per
operation name and gap labels are the hand-checked values; and on one
made-up serving step, where the program's ``serve/`` spans name the gaps.

The fixture (``fixtures/v5e_train_step.trace.json``) keeps the operation
names as a v5e recorded them in PR 24's first chip run; its times are
rounded to whole microseconds and the layer scan is cut to two iterations,
so that the sums below can be done by hand:

    window          1,000 .. 20,000 us                        = 19.0 ms
    busy            1,000..9,000 + 12,000..15,000 + 15,400..16,000
                                                              = 11.6 ms
    idle share      1 - 11.6 / 19.0                           = 0.389474
    gaps >= 0.5 ms  9,000..12,000 (3.0 ms, host in loss_fetch),
                    16,000..20,000 (4.0 ms, host in loss_fetch)
    while.8 self    (6.0 - 1.0 - 0.5 - 1.5 - 1.0 - 1.5) + (2.0 - 2.0)
                                                              = 0.5 ms
"""

import os

import pytest

from benchmark import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_train_step.trace.json")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(trace_reduce.load_json(FIXTURE))


def test_window_busy_and_idle(reduced):
    assert reduced["window_s"] == pytest.approx(19.0e-3)
    assert reduced["busy_s"] == pytest.approx(11.6e-3)
    assert reduced["idle_share"] == pytest.approx(1 - 11.6 / 19.0)
    assert reduced["n_devices"] == 1


def test_self_time_per_operation(reduced):
    ops = reduced["op_self_s"]
    assert ops["flash_attention_fwd.17"] == pytest.approx(4.0e-3)
    assert ops["flash_attention_bwd_dq.9"] == pytest.approx(1.5e-3)
    assert ops["fusion.364"] == pytest.approx(2.6e-3)   # not the 0.2 ms
    assert ops["jvp_fused_ce_fwd_.1"] == pytest.approx(3.0e-3)
    assert ops["while.8"] == pytest.approx(0.5e-3)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"])
    assert reduced["op_calls"]["flash_attention_fwd.17"] == 3


def test_kernel_family(reduced):
    assert trace_reduce.family_time(reduced, "flash_attention_fwd") \
        == (pytest.approx(4.0e-3), 3)
    assert trace_reduce.family_time(reduced, "fused_ce_fwd") \
        == (pytest.approx(3.0e-3), 2)
    assert trace_reduce.family_time(reduced, "paged_append_attend") == (0.0, 0)


def test_gaps_are_labelled_by_the_host_span(reduced):
    assert [(label, pytest.approx(s)) for label, s in reduced["gaps"]] == [
        ("bench/loss_fetch", 4.0e-3), ("bench/loss_fetch", 3.0e-3)]
    b = trace_reduce.breakdown(reduced)
    assert b["device_ops"][0] == ["flash_attention_fwd.17",
                                  pytest.approx(4.0e-3)]
    assert b["idle_gaps"] == [["bench/loss_fetch", pytest.approx(7.0e-3)]]


def _serving_trace(with_program_spans=True):
    """One serving step in the neutral form, times in ms: the device runs
    0..1, 3..4, 9..10, 12..13 and 15..16 of a window of 0..20, so the gaps
    are 1..3, 4..9, 10..12, 13..15 and 16..20."""
    ms = 1e6
    ops = [("%fusion.1 = bf16[8]{0} fusion(%p)", t * ms, 1 * ms)
           for t in (0, 3, 9, 12, 15)]
    host = [("bench/traced_window", 0, 20 * ms),
            ("bench/frontend_step", 0, 16.5 * ms),
            ("bench/harvest", 16.5 * ms, 1 * ms)]
    if with_program_spans:
        host += [("serve/frontend_step", 0.1 * ms, 16.3 * ms),
                 ("serve/step", 0.2 * ms, 16.1 * ms),
                 ("serve/admit", 0.5 * ms, 8.5 * ms),         # 0.5 .. 9
                 ("serve/dispatch", 4 * ms, 4 * ms),          # 4 .. 8
                 ("serve/harvest", 13.2 * ms, 3 * ms),        # 13.2 .. 16.2
                 ("serve/device_wait", 13.4 * ms, 0.4 * ms)]
    return {"/device:TPU:0": {"XLA Ops": ops}, "/host:CPU": {"python": host}}


def test_gaps_are_labelled_by_the_innermost_program_span():
    reduced = trace_reduce.reduce_trace(_serving_trace())
    labels = sorted((round(s * 1e3, 3), label)
                    for label, s in reduced["gaps"])
    assert labels == [
        (2.0, "serve/admit"),    # 1..3: inside the admission, no dispatch
        (2.0, "serve/harvest"),  # 13..15: 1.8 of 2 in harvest; the wait
                                 # inside it is too short to hold half
        (2.0, "serve/step"),     # 10..12: the step, nothing inside it
        (4.0, "bench/harvest"),  # 16..20: no span holds half of it
        (5.0, "serve/dispatch"),
    ]


def test_program_spans_change_the_labels_and_nothing_else():
    """What ``bench/frontend_step`` read before, the ``serve/`` labels sum
    to now; gaps, busy time and the idle share are the same numbers."""
    before = trace_reduce.reduce_trace(_serving_trace(False))
    after = trace_reduce.reduce_trace(_serving_trace(True))
    for key in ("window_s", "busy_s", "idle_share", "op_self_s", "op_calls"):
        assert before[key] == after[key]
    assert sorted(s for _, s in before["gaps"]) \
        == sorted(s for _, s in after["gaps"])
    old = dict(trace_reduce.breakdown(before)["idle_gaps"])
    new = dict(trace_reduce.breakdown(after)["idle_gaps"])
    assert old == {"bench/frontend_step": pytest.approx(11e-3),
                   "bench/harvest": pytest.approx(4e-3)}
    assert sum(s for label, s in new.items() if label.startswith("serve/")) \
        == pytest.approx(old["bench/frontend_step"])
    assert new["bench/harvest"] == pytest.approx(old["bench/harvest"])


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"/host:CPU": {"python": []}})
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"/device:TPU:0": {"XLA Ops": []}})
