"""The general traffic generator: every seed gets the same multiset of
lengths in another order, residual first answers, shared prefixes."""

import json
import os

from benchmark import harness, traffic_gen


def _mix(**changes):
    mix = harness.load_json("traffic", "serve-chat16.json")
    mix.update(changes)
    return mix


def test_quantile_lengths_are_clipped_and_median_centred():
    spec = {"dist": "lognormal", "median": 160, "sigma": 0.8,
            "min": 16, "max": 512}
    lens = traffic_gen.quantile_lengths(spec, 64)
    assert len(lens) == 64 and min(lens) >= 16 and max(lens) == 512
    assert lens == sorted(lens)
    assert 150 <= lens[31] <= 160 <= lens[32] <= 170     # straddles the median
    # a source that states a mean: median = mean * exp(-sigma^2 / 2)
    by_mean = traffic_gen.quantile_lengths(
        dict(spec, mean=160 * 2.718281828459045 ** 0.32), 64)
    del spec["median"]
    assert by_mean == lens
    assert traffic_gen.quantile_lengths(
        {"dist": "uniform", "min": 10, "max": 20}, 5) == [11, 13, 15, 17, 19]
    assert traffic_gen.quantile_lengths(
        {"dist": "fixed", "value": 7}, 3) == [7, 7, 7]


def test_the_mix_keeps_its_sources_means_and_fits_its_pool():
    mix = _mix()
    n = mix["request_pool"]
    prompts = traffic_gen.quantile_lengths(mix["prompt_len"], n)
    answers = traffic_gen.quantile_lengths(mix["answer_len"], n)
    # Alpaca as the vLLM paper measures it: mean input 19.31, output 58.45
    assert abs(sum(prompts) / n - 19.31) < 0.5
    assert abs(sum(answers) / n - 58.45) < 1.5
    longest = traffic_gen.longest_request(mix)
    assert longest == max(prompts) + max(answers) <= 2048
    assert max(prompts) <= 512          # the paged engine admits no longer
    # every slot can hold the longest request at once (pages of 128)
    assert mix["max_slots"] * -(-(longest + 2) // 128) <= mix["kv_pool_pages"]


def _free_order():
    mix = _mix()
    del mix["order_seed"]
    return mix


def test_every_seed_gets_the_same_lengths_in_another_order():
    a = traffic_gen.ClosedLoopTraffic(_free_order(), 1, 50304)
    b = traffic_gen.ClosedLoopTraffic(_free_order(), 2**31 + 5, 50304)
    assert sorted(p for p, _ in a.pool) == sorted(p for p, _ in b.pool)
    assert sorted(n for _, n in a.pool) == sorted(n for _, n in b.pool)
    assert a.pool != b.pool


def test_a_fixed_order_gives_every_seed_the_same_work_on_other_tokens():
    a = traffic_gen.ClosedLoopTraffic(_mix(), 1, 50304)
    b = traffic_gen.ClosedLoopTraffic(_mix(), 2**31 + 5, 50304)
    assert a.pool == b.pool
    ra = [a.next_request(c) for c in range(16)]
    rb = [b.next_request(c) for c in range(16)]
    assert [(len(p), n) for p, n in ra] == [(len(p), n) for p, n in rb]
    assert [p for p, _ in ra] != [p for p, _ in rb]


def test_requests_come_from_the_seed_and_first_answers_are_residuals():
    mix = _mix()
    one = traffic_gen.ClosedLoopTraffic(mix, 7, 50304)
    two = traffic_gen.ClosedLoopTraffic(mix, 7, 50304)
    firsts = [one.next_request(c) for c in range(mix["clients"])]
    assert firsts == [two.next_request(c) for c in range(mix["clients"])]
    for (prompt, asked), (n_prompt, n_answer) in zip(firsts, one.pool):
        assert len(prompt) == n_prompt and 1 <= asked <= n_answer
        assert all(0 <= t < 50304 for t in prompt)
    assert len({asked / n for (_, asked), (_, n) in zip(firsts, one.pool)}) > 8
    prompt, asked = one.next_request(0)         # a client's second request
    assert asked == one.pool[mix["clients"]][1]


def test_shared_prefix():
    gen = traffic_gen.ClosedLoopTraffic(_mix(shared_prefix_tokens=12), 3, 999)
    a, _ = gen.next_request(0)
    b, _ = gen.next_request(1)
    n = min(len(a), len(b), 13) - 1
    assert a[:n] == b[:n] and a != b
