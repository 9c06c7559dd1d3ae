"""The schedule generator: the same schedule for every seed, prompts from
the seed, lengths within the clip."""
import numpy as np
import pytest

from chipbench import traffic

MIX = {"arrivals": {"process": "poisson", "rate_rps": 5.0},
       "lengths": {"dist": "lognormal", "mean": 768, "sigma": 0.8,
                   "min": 128, "max": 2048},
       "out_len": 1}


def as_tuples(arr):
    return [(a.rid, a.due, a.length) for a in arr]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, -3, 2**70 + 1])
def test_same_seed_same_schedule(seed):
    a = traffic.schedule(MIX, 30.0)
    b = traffic.schedule(MIX, 30.0)
    assert as_tuples(a) == as_tuples(b)
    ta = traffic.prompt_tokens(a, 1000, seed)
    tb = traffic.prompt_tokens(b, 1000, seed)
    assert all(np.array_equal(ta[k], tb[k]) for k in ta)


def test_other_seed_other_prompts_same_schedule():
    arr = traffic.schedule(MIX, 30.0)
    assert len(arr) == 150
    ta = traffic.prompt_tokens(arr, 1000, 1)
    tb = traffic.prompt_tokens(arr, 1000, 2)
    assert all(len(ta[k]) == len(tb[k]) == a.length
               for k, a in zip(ta, arr))
    assert all(not np.array_equal(ta[k], tb[k]) for k in ta)
    # the order is mixed: neither lengths nor gaps come sorted
    lengths = [a.length for a in arr]
    gaps = np.diff([a.due for a in arr])
    assert lengths != sorted(lengths) and list(gaps) != sorted(gaps)


def test_lengths_within_the_clip_and_rate_exact():
    arr = traffic.schedule(MIX, 40.0)
    lengths = np.array([a.length for a in arr])
    assert lengths.min() >= 128 and lengths.max() <= 2048
    assert lengths.max() == 2048 and lengths.min() == 128  # the clip bites
    assert 600 < lengths.mean() < 800
    due = np.array([a.due for a in arr])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 40.0
    assert len(arr) == 200


@pytest.mark.parametrize("bad", [
    {"arrivals": {"process": "gamma", "rate_rps": 1.0, "cv": 3.0},
     "lengths": MIX["lengths"]},
    {"arrivals": MIX["arrivals"], "lengths": {"dist": "fixed", "length": 128}},
], ids=["process", "dist"])
def test_unknown_process_or_length_distribution_is_refused(bad):
    with pytest.raises(ValueError):
        traffic.schedule(bad, 10.0)
