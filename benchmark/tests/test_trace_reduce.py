"""The trace reduction on the small recorded trace: idle share, time per
operation name and gap labels are the hand-checked values.

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


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"/host:CPU": {"python": []}})
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"/device:TPU:0": {"XLA Ops": []}})
