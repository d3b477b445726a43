"""The one traffic generator: a workload file's parameters and a seed
give the requests of a run.

Stdlib only (the load generator's side never imports numpy or JAX).
The idiom is `cloud_server_tpu/scenarios/workload.py`'s (seeded
`random.Random`, lognormal lengths), copied here so
the yardstick does not move when the program does.

Every seed gets the SAME multiset of (prompt length, answer length)
pairs: one block of them is fixed by the file (a stratified sample of
each distribution), the traffic is that block over and over, and
`--seed` only deals each copy in another order and draws the token ids.
So two seeds, and two stretches of one run, do the same work in another
order, and a difference between them is not a difference in work.
"""

from __future__ import annotations

import math
import random
import statistics


def _quantile_lengths(spec: dict, n: int) -> list[int]:
    """n lengths at the mid-quantiles of {"median", "sigma", "min",
    "max"}: lognormal, rounded to whole tokens, clipped. A stratified
    sample: its mean and its tails are the distribution's, with none of
    a random sample's luck."""
    nd = statistics.NormalDist()
    lo, hi = int(spec["min"]), int(spec["max"])
    return [max(lo, min(hi, round(spec["median"] * math.exp(
        spec["sigma"] * nd.inv_cdf((i + 0.5) / n))))) for i in range(n)]


def population(spec: dict) -> list[tuple[int, int]]:
    """The fixed block of a workload: `block` pairs of (prompt length,
    answer length), each side a stratified sample of its distribution,
    paired at random once. Depends on the file alone, never on
    `--seed`."""
    rng = random.Random(int(spec["population_seed"]))
    n = int(spec["block"])
    prompts = _quantile_lengths(spec["prompt_len"], n)
    answers = _quantile_lengths(spec["answer_len"], n)
    rng.shuffle(answers)
    return list(zip(prompts, answers))


def make_requests(spec: dict, seed: int, vocab_size: int) -> list[dict]:
    """The requests of one run: `blocks` copies of the block, each dealt
    in an order of its own from `seed`, token ids uniform over the
    vocabulary from the same generator. Any stretch of the list
    therefore holds nearly the same work, whatever the seed. Clients of
    the closed loop take requests in list order."""
    pairs = population(spec)
    rng = random.Random(f"{int(seed)}/window")
    out = []
    for _ in range(int(spec["blocks"])):
        block = list(range(len(pairs)))
        rng.shuffle(block)
        for idx in block:
            plen, alen = pairs[idx]
            out.append({
                "id": len(out),
                "tokens": [rng.randrange(1, vocab_size)
                           for _ in range(plen)],
                "max_new": alen,
            })
    return out
