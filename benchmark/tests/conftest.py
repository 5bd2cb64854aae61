"""Tiny stand-ins for the cells, for the CPU: same files, same code paths,
sizes a test run can hold."""

import copy
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

TINY_MODEL = {
    "n_layers": 2, "d_model": 128, "n_heads": 2, "head_dim": 64,
    "ffn_mult": 4, "d_ffn": 512, "vocab_size": 512, "max_seq_len": 256,
    "use_bias": True, "tie_embeddings": True, "dtype": "bfloat16",
}


@pytest.fixture
def tiny_train_cell():
    from benchmark import harness
    cell = copy.deepcopy(harness.load_cell("gpt3-xl.train-2k"))
    cell["model"] = dict(TINY_MODEL)
    cell["traffic"].update(batch=4, seq_len=256, traced_steps=2)
    return cell


@pytest.fixture
def tiny_serve_cell():
    from benchmark import harness
    cell = copy.deepcopy(harness.load_cell("gpt3-xl.serve-chat16"))
    cell["model"] = dict(TINY_MODEL)
    cell["traffic"].update(
        clients=4, max_slots=4, request_pool=8, kv_pool_pages=8,
        checked_requests=3,
        traced_seconds=1,
        prompt_len={"dist": "lognormal", "median": 40, "sigma": 0.8,
                    "min": 16, "max": 128},
        answer_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                    "min": 4, "max": 16})
    return cell
