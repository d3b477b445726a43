"""The plain reference against the system at tiny widths on the CPU, and
the controls failing: the served log-probabilities with the program's
int8 weights or int8 cache are far from the reference's where the
program as the cell runs it is at rounding. The expert block through
the cell's own configuration, the dense block through the same file
with the experts taken out."""
import json

import numpy as np
import pytest

from cellbench import check_seeds, reference, rehearse, run

CELL = run.load_benchmark()["workloads"][0]["name"]


@pytest.mark.parametrize("control", ["quantize", "kv-int8"])
@pytest.mark.parametrize("block", ["experts", "dense"])
def test_reference_matches_and_int8_control_fails(block, control):
    bench = run.load_benchmark()
    _, wl, cfg = run.load_cell(bench, CELL)
    if block == "dense":
        cfg = json.loads(json.dumps(cfg))
        cfg["num_local_experts"] = 0
    args = (rehearse.TINY, [272, 300], 2, 8)
    sound = check_seeds.one_seed(wl, cfg, 2**31 + 11, "", *args)
    control = check_seeds.one_seed(wl, cfg, 2**31 + 11, control, *args)
    # float32 end to end: the paged server equals the plain forward to
    # rounding, through chunked prefill, the cache and decode
    assert sound["finite"] and sound["logprob_mean_abs_diff"] < 2e-5
    assert sound["margin_max"] < 1e-4
    assert sound["stable_diff_over_share"] == 0
    # the same run in int8 is a hundred times farther
    assert control["logprob_median_abs_diff"] > \
        100 * sound["logprob_median_abs_diff"]
    assert control["logprob_mean_abs_diff"] > 5e-4
    # a dense model has no router: every token is stable
    assert (sound["stable_share"] == 1.0) == (block == "dense")


def test_compare_sees_a_fault_in_a_few_tokens_in_a_hundred():
    """What the median cannot see: 3 tokens in a hundred wrong by a
    whole unit of log-probability, the rest at rounding. And a token the
    router nearly sent elsewhere is set apart, not counted."""
    rng = np.random.default_rng(0)
    n = 1000
    ref = rng.normal(-8.0, 1.0, n)
    served = ref + rng.normal(0.0, 0.01, n)
    per_token = {"served": served.tolist(), "ref": ref.tolist(),
                 "margin": [0.0] * n, "gap": [1.0] * n}
    sound = reference.compare(per_token, stable_gap=0.1, diff_over=0.25)
    assert sound["stable_diff_over_share"] == 0.0
    assert sound["logprob_median_abs_diff"] < 0.01
    bad = np.array(served)
    bad[::33] += 1.0
    per_token["served"] = bad.tolist()
    faulty = reference.compare(per_token, stable_gap=0.1, diff_over=0.25)
    assert faulty["stable_diff_over_share"] == pytest.approx(0.031)
    assert faulty["logprob_median_abs_diff"] < 0.01  # the median is blind
    gaps = np.ones(n)
    gaps[::33] = 0.01  # the same tokens, but the router was undecided
    per_token["gap"] = gaps.tolist()
    flips = reference.compare(per_token, stable_gap=0.1, diff_over=0.25)
    assert flips["stable_diff_over_share"] == 0.0
    assert flips["stable_share"] == pytest.approx(0.969)
