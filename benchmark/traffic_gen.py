"""The one general generator of serving traffic. A traffic mix is a data
file under ``traffic/``; this reads its parameters and makes the requests
from ``--seed``.

Every seed gets the SAME multiset of lengths: the ``request_pool`` prompt
lengths and as many answer lengths are the evenly spaced quantiles of the
file's clipped distributions. What pairs them, orders them and deals them
to the clients is the file's ``order_seed`` where it has one, else
``--seed``; the token ids always come from ``--seed``. In a closed loop the
ORDER of the lengths decides which requests meet in one engine step, and a
tail of the token gaps follows it (PERF.md section 2): a mix that judges
such a tail fixes the order, so that every seed does the same work at the
same moments on other tokens.

A length distribution is ``lognormal`` (``sigma`` with either the ``mean``
or the ``median`` its source states), ``uniform`` or ``fixed``, clipped to
``min``..``max``. Keys of a traffic file that start with ``_`` (``_source``,
``_why``) are for the reader and not for the generator.
"""

import statistics

import numpy as np


def quantile_lengths(spec: dict, n: int) -> list:
    """``n`` lengths at the quantiles (i + 0.5) / n of the distribution."""
    if spec["dist"] == "lognormal":
        normal = statistics.NormalDist()
        median = (spec["median"] if "median" in spec else
                  spec["mean"] * np.exp(-0.5 * spec["sigma"] ** 2))
        raw = [median * np.exp(spec["sigma"]
                                       * normal.inv_cdf((i + 0.5) / n))
               for i in range(n)]
    elif spec["dist"] == "uniform":
        raw = [spec["min"] + (spec["max"] - spec["min"]) * (i + 0.5) / n
               for i in range(n)]
    elif spec["dist"] == "fixed":
        raw = [spec["value"]] * n
        return [int(v) for v in raw]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(max(round(v), spec["min"]), spec["max"])) for v in raw]


def longest_request(traffic: dict) -> int:
    """Prompt plus answer of the longest request any seed can draw: the
    longest prompt paired with the longest answer."""
    n = traffic["request_pool"]
    return (max(quantile_lengths(traffic["prompt_len"], n))
            + max(quantile_lengths(traffic["answer_len"], n)))


class ClosedLoopTraffic:
    """``clients`` callers, each sending its next request the moment its
    last one ends. ``next_request(client)`` gives (prompt, answer_len).

    Each client's FIRST request has its answer cut to a residual drawn
    evenly over (0, answer_len]: the clients then start at different
    points of their requests, as they are in steady state, and the window
    need not wait until each has finished a whole request."""

    def __init__(self, traffic: dict, seed: int, vocab_size: int):
        self.rng = np.random.default_rng([int(seed), 0x73657276])
        order = np.random.default_rng(
            [int(traffic.get("order_seed", seed)), 0x6F72646572])
        self.vocab = vocab_size
        n = traffic["request_pool"]
        self.clients = traffic["clients"]
        prompts = quantile_lengths(traffic["prompt_len"], n)
        answers = quantile_lengths(traffic["answer_len"], n)
        self.pool = list(zip(order.permutation(prompts).tolist(),
                             order.permutation(answers).tolist()))
        self.shared = int(traffic.get("shared_prefix_tokens", 0))
        self.prefix = self.rng.integers(
            0, vocab_size, size=self.shared).tolist()
        self._cursor = 0
        fractions = (np.arange(self.clients) + 0.5) / self.clients
        self._residual = order.permutation(fractions).tolist()
        self._first = [True] * self.clients

    def next_request(self, client: int):
        n_prompt, n_answer = self.pool[self._cursor % len(self.pool)]
        self._cursor += 1
        if self._first[client]:
            self._first[client] = False
            n_answer = max(1, int(round(n_answer * self._residual[client])))
        own = max(1, n_prompt - self.shared)
        prompt = self.prefix[:n_prompt - own] + self.rng.integers(
            0, self.vocab, size=own).tolist()
        return prompt, n_answer
