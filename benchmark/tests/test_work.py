"""The work counts against hand arithmetic."""

import json
import os

import pytest

from benchmark import peaks, work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_train_flops_gpt3_xl_at_2k():
    # 24 * 12 * 2048^2 = 1,207,959,552; 50304 * 2048 = 103,022,592
    # 6 * 1,310,982,144 = 7,865,892,864; 6 * 24 * 2048 * 2048 = 603,979,776
    assert work.train_flops_per_token(model("gpt3-xl"), 2048) == 8_469_872_640
    assert work.train_flops_per_token(model("gpt3-xl"), 2048) \
        == pytest.approx(8.47e9, rel=1e-3)


@pytest.mark.parametrize("seq_len, exact, rounded", [
    # 24 * 12 * 1024^2 = 301,989,888; 50304 * 1024 = 51,511,296
    # 6 * 353,501,184 = 2,121,007,104
    (2048, 2_422_996_992, 2.42e9),      # + 6 * 24 * 1024 * 2048 = 301,989,888
    (8192, 3_328_966_656, 3.33e9),      # + 6 * 24 * 1024 * 8192 = 1,207,959,552
])
def test_train_flops_gpt3_medium(seq_len, exact, rounded):
    flops = work.train_flops_per_token(model("gpt3-medium"), seq_len)
    assert flops == exact
    assert flops == pytest.approx(rounded, rel=2e-3)


def test_serve_flops():
    m = model("gpt3-xl")
    blocks = 2 * 24 * 12 * 2048 ** 2           # per token, all layers
    head = 2 * 50304 * 2048
    assert work.decode_flops(m, 300) == blocks + 4 * 24 * 2048 * 300 + head
    # a prompt's attention: sum over its tokens of 4 * L * d * context
    assert work.prefill_flops(m, 3) \
        == 3 * blocks + 4 * 24 * 2048 * (1 + 2 + 3) + head


def test_flash_kernel_work_and_bound():
    m = model("gpt3-medium")
    w = work.flash_kernel_work(m, 2, 8192)
    one = 2 * 2 * 16 * 8192 * 8192 * 64 // 2    # one causal product
    assert w["flash_attention_fwd"][0] == 2 * one
    assert w["flash_attention_bwd_dq"][0] == 3 * one
    assert w["flash_attention_bwd_dkdv"][0] == 4 * one
    tensor = 2 * 16 * 8192 * 64 * 2
    assert w["flash_attention_fwd"][1] == 4 * tensor + 2 * 16 * 8192 * 4
    seconds, bound = work.least_seconds(*w["flash_attention_fwd"],
                                        peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute"
    assert seconds == pytest.approx(2 * one / 197e12)


def test_paged_attend_is_memory_bound():
    m = model("gpt3-xl")
    flops, nbytes = work.paged_attend_work(m, [100, 300])
    assert nbytes == 2 * 16 * 128 * 2 * 400      # K and V, bf16
    assert flops == 4 * 16 * 128 * 400
    seconds, bound = work.least_seconds(flops, nbytes,
                                        peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and seconds == pytest.approx(nbytes / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(RuntimeError, match="no row"):
        peaks.peaks_for("cpu")
