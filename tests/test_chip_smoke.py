"""chip_smoke.py on the CPU: it must refuse to run without a TPU, and its
phases must pass at ``gpt_tiny`` once the platform check is stepped over
HERE (monkeypatched — the script has no option that skips it). This is
the guide's rehearsal 1 (end to end, tiny, interpret-mode kernels) and
rehearsal 2 (the four-chip phase on virtual devices) kept as tests."""

import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu.models import gpt  # noqa: E402


@pytest.fixture
def stepped_over(monkeypatch):
    """Step over what only a chip can satisfy (the platform check and
    the tpu_custom_call reading of the compiled text) and cut the run
    to gpt_tiny sizes; restore the process-global cache setting
    ``compile_cache.enable()`` touches."""
    monkeypatch.setattr(chip_smoke, "_require_tpu", lambda jax, chips: None)
    monkeypatch.setattr(chip_smoke, "_require_kernels",
                        lambda text, names, what: None)
    monkeypatch.setattr(chip_smoke, "model_config",
                        lambda: gpt.gpt_tiny(max_seq_len=192, remat=True))
    monkeypatch.setattr(chip_smoke, "PROMPT_LENS",
                        (8, 24, 60, 100, 120, 40, 90, 110))
    monkeypatch.setattr(chip_smoke, "NEW_TOKENS", 12)
    monkeypatch.setattr(chip_smoke, "POOL_TOKENS_PER_SLOT", 256)
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _last_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return lines, json.loads(lines[-1])


def test_refuses_without_a_tpu(monkeypatch, capsys):
    """No accelerator: a non-zero exit BEFORE any work, no result line."""
    def no_work():
        raise AssertionError("chip_smoke built a model without a TPU")
    monkeypatch.setattr(chip_smoke, "model_config", no_work)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_train_and_serve_phases_pass_at_tiny(stepped_over, capsys):
    assert chip_smoke.main(["--seed", "3"]) == 0
    lines, last = _last_line(capsys)
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    out = "\n".join(lines)
    assert "serve: PagedDecodeEngine (page" in out
    assert out.count("identical to gpt.generate") == 2   # f32: bit-exact
    assert "compile cache at" in out and "train: compile" in out


def test_four_chip_phase_on_virtual_devices(stepped_over, capsys):
    """--chips 4 runs ONLY the sharded step and its one-chip comparison
    (here on 4 of the virtual CPU devices), and shows the state spread."""
    assert chip_smoke.main(["--chips", "4"]) == 0
    lines, last = _last_line(capsys)
    assert last["ok"] is True
    out = "\n".join(lines)
    assert "sharded optimizer state:" in out and "serve:" not in out
