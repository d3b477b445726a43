"""A traffic generator of several populations of prompts on one queue: a
workload file's parameters and a seed give the requests of a run.

`traffic.py` draws every prompt from one distribution. This one deals a
block that holds `count` prompts of each of `populations` (chat turns
beside questions over long documents, say), and every answer from one
distribution. Everything else is `traffic.py`'s, and for its reasons:
stdlib only, each side a stratified sample (`traffic._quantile_lengths`)
so that the block's mean and tails are the distributions' with none of a
random sample's luck, the block fixed by the file alone
(`population_seed` pairs prompts with answers once), and `--seed` only
deals each copy of the block in another order and draws the token ids: two
seeds, and two stretches of one run, do the same work in another order.
"""

from __future__ import annotations

import random

from cellbench.traffic import _quantile_lengths


def population(spec: dict) -> list[tuple[int, int]]:
    """The fixed block: for each entry of `populations`, `count` prompt
    lengths at the mid-quantiles of its `prompt_len`; as many answer
    lengths at the mid-quantiles of `answer_len`, paired at random once.
    Depends on the file alone, never on `--seed`."""
    rng = random.Random(int(spec["population_seed"]))
    prompts = []
    for pop in spec["populations"]:
        prompts += _quantile_lengths(pop["prompt_len"], int(pop["count"]))
    if len(prompts) != int(spec["block"]):
        raise ValueError(f"the populations hold {len(prompts)} prompts, "
                         f"the block {spec['block']}")
    answers = _quantile_lengths(spec["answer_len"], len(prompts))
    rng.shuffle(answers)
    return list(zip(prompts, answers))


def make_requests(spec: dict, seed: int, vocab_size: int) -> list[dict]:
    """The requests of one run: `blocks` copies of the block, each dealt
    in an order of its own from `seed`, token ids uniform over the
    vocabulary from the same generator. Clients of the closed loop take
    requests in list order."""
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
