"""The traffic generator and the timeline arithmetic: no JAX, no server."""
import json
import math
import os

import pytest

from cellbench import stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
WORKLOADS = os.path.join(os.path.dirname(HERE), "workloads")


def load(name):
    with open(os.path.join(WORKLOADS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(WORKLOADS) if f.endswith(".json")))
def test_same_seed_same_bytes_other_seed_other_order(name):
    wl = load(name)
    a = traffic.make_requests(wl, 2**31 + 12345, 32768)
    b = traffic.make_requests(wl, 2**31 + 12345, 32768)
    c = traffic.make_requests(wl, 7, 32768)
    assert json.dumps(a) == json.dumps(b)
    assert json.dumps(a) != json.dumps(c)
    n = int(wl["block"])
    assert len(a) == n * int(wl["blocks"])

    def work(rs):
        return sorted((len(r["tokens"]), r["max_new"]) for r in rs)
    # every block of every seed holds the same work, in another order
    assert work(a[:n]) == work(c[:n]) == work(a[n:2 * n]) == work(c[-n:])
    assert [len(r["tokens"]) for r in a[:n]] != \
        [len(r["tokens"]) for r in c[:n]]
    lo, hi = wl["prompt_len"]["min"], wl["prompt_len"]["max"]
    assert all(lo <= len(r["tokens"]) <= hi for r in a)
    assert all(1 <= t < 32768 for r in a[:3] for t in r["tokens"])


def test_the_block_is_a_stratified_sample_of_the_stated_lengths():
    wl = load("batch-closed")
    pairs = traffic.population(wl)
    prompts = sorted(p for p, _ in pairs)
    answers = sorted(a for _, a in pairs)
    n = len(pairs)
    # mid-quantiles of a lognormal: the middle of the block is the median
    assert abs(prompts[n // 2] - wl["prompt_len"]["median"]) < 16
    assert abs(answers[n // 2] - wl["answer_len"]["median"]) < 6
    assert prompts[0] == wl["prompt_len"]["min"]
    assert prompts[-1] == wl["prompt_len"]["max"]
    # whole tokens, not whole chunks: last chunks of every width bucket
    # and prompts of three admission buckets
    chunk = wl["server"]["prefill_chunk"]
    last = {(p - 1) % chunk + 1 for p in prompts}
    for lo, hi in ((1, 16), (17, 32), (33, 64), (65, 128), (129, 256)):
        assert any(lo <= x <= hi for x in last)
    assert {1 << (p - 1).bit_length() for p in prompts} == {512, 1024, 2048}
    assert len(set(prompts)) > n // 2


def test_pct():
    assert stats.pct([1, 2, 3, 4, 5], 0.5) == 3
    assert stats.pct([10, 20], 0.95) == pytest.approx(19.5)
    assert math.isnan(stats.pct([], 0.5))


def timeline():
    # window [10, 20): one request wholly inside, one straddling the
    # start, one sent inside with no token by the end, one whose first
    # token comes after the end, one refused, one broken by the
    # tear-down after the end
    return [
        {"sent": 11.0, "token_times": [11.5, 11.6, 11.8],
         "error": None},
        {"sent": 9.0, "token_times": [9.5, 9.9, 10.1, 10.4],
         "error": None},
        {"sent": 18.0, "token_times": [], "error": None},
        {"sent": 19.5, "token_times": [20.5], "error": None},
        {"sent": 12.0, "token_times": [],
         "error": "http 429", "ended": 12.01},
        {"sent": 17.0, "token_times": [17.5, 17.6],
         "error": "server stopped", "ended": 20.05},
    ]


def test_window_arithmetic_on_a_hand_made_timeline():
    recs = timeline()
    assert stats.tokens_in_window(recs, 10.0, 20.0) == 7
    samples, censored = stats.ttft_samples(recs, 10.0, 20.0)
    # sent 11 -> 0.5; sent 18, no token -> censored at 2.0; sent 19.5,
    # first token after the end -> censored at 0.5; refused -> censored
    # at 8.0
    assert sorted(samples) == pytest.approx([0.5, 0.5, 0.5, 2.0, 8.0])
    assert censored == 3
    gaps = stats.gap_samples(recs, 10.0, 20.0)
    # 11.5->11.6, 11.6->11.8, 10.1->10.4; 9.9->10.1 starts before
    assert sorted(gaps) == pytest.approx([0.1, 0.1, 0.2, 0.3])
    # an error after the window's end is the tear-down's, not a failure
    assert stats.count_attempted_failed(recs, 10.0, 20.0) == (5, 1)
