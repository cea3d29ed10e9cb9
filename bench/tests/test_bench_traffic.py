"""The traffic generator: one seed, one plan; every seed the same work."""
import numpy as np
import pytest

from bench.traffic_gen import load_traffic, make_plan


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_plan_repeats_for_a_seed_and_keeps_its_clips(mix):
    t = load_traffic(mix)
    a = make_plan(t, 2 ** 40 + 3, 45, 32, 151936)
    b = make_plan(t, 2 ** 40 + 3, 45, 32, 151936)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    assert a.prompt(5) == b.prompt(5)
    assert t["prompt"]["min"] <= a.prompt_lens.min()
    assert a.prompt_lens.max() <= t["prompt"]["max"]
    assert t["output"]["min"] <= a.output_lens.min()
    assert a.output_lens.max() <= t["output"]["max"]
    assert a.prompt_lens.max() + a.output_lens.max() <= t["max_len"]
    assert all(0 <= x < 151936 for x in a.prompt(0))
    assert len(a.prompt(3)) == a.prompt_lens[3]


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_seeds_reorder_the_same_work(mix):
    t = load_traffic(mix)
    a = make_plan(t, 1, 45, 32, 1000)
    b = make_plan(t, 2, 45, 32, 1000)
    assert sorted(a.prompt_lens) == sorted(b.prompt_lens)
    assert sorted(a.output_lens) == sorted(b.output_lens)
    assert not np.array_equal(a.prompt_lens, b.prompt_lens)
    assert a.prompt(0) != b.prompt(0)
    if t["loop"] == "open":
        for plan in (a, b):
            rate = len(plan.due) / plan.due[-1]
            assert rate == pytest.approx(t["rate_per_s"], rel=0.05)


def test_lognormal_medians():
    t = load_traffic("chat")
    p = make_plan(t, 0, 45, 32, 1000)
    assert np.median(p.prompt_lens) == pytest.approx(512, abs=2)
    assert np.median(p.output_lens) == pytest.approx(128, abs=2)


def test_every_block_gets_one_value_of_each_stratum():
    from bench.traffic_gen import spread_over_blocks
    t = load_traffic("chat")
    k = t["block_requests"]
    a = make_plan(t, 2 ** 33 + 5, 51, 32, 1000)
    b = make_plan(t, 7, 51, 32, 1000)
    assert len(a) % k == 0
    gaps = np.diff(a.due, prepend=0.0)
    for plan in (a, b):
        for lens in (plan.prompt_lens, plan.output_lens):
            strata = np.sort(lens).reshape(k, -1)
            for blk in lens.reshape(-1, k):
                lo, hi = strata[:, 0], strata[:, -1]
                assert (np.sort(blk) >= lo).all()
                assert (np.sort(blk) <= hi).all()
    # every block but the first (which starts at 0) spans about k / rate
    spans = a.due[k::k] - a.due[:-k:k]
    assert spans == pytest.approx(k / t["rate_per_s"], rel=0.5)
    assert gaps.sum() > 0
    v = np.arange(20.0)
    assert sorted(spread_over_blocks(v, 1, np.random.default_rng(0))) == \
        list(v)
