"""The control: the reference computed in float8 e4m3, the precision below
the configurations' bfloat16, put in the program's place, has to fail the
comparison that decides `correct` (`bench.check`).  Here at a test size on
the CPU; the chip readings at the cells' own sizes, from which each limit
was set, are in PERF.md."""
import json
import os

import numpy as np
import pytest

from chipbench import bench, manifest, traffic
from chipbench.control import control_verdict

HERE = os.path.dirname(__file__)
reference = manifest.load_reference(manifest.DEFAULT_REFERENCE)


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "fixtures", "tiny.json")) as f:
        config = json.load(f)
    m = config["model"]
    return m, reference.weights(m, 31337), config["correct"]


def served(m, w, seed, n=48):
    """Requests answered with the reference's own final hidden state and
    first token, as a sound program would answer them, and their
    prompts."""
    mix = {"arrivals": {"process": "poisson", "rate_rps": 1.0},
           "lengths": {"dist": "lognormal", "mean": 40, "sigma": 0.8,
                       "min": 8, "max": 64}}
    arr = traffic.schedule(mix, n)
    toks = traffic.prompt_tokens(arr, m["vocab_size"], seed)
    reqs = []
    for a in arr:
        h = reference.hidden_states(m, w, toks[a.rid])
        reqs.append(bench.Request(
            rid=a.rid, length=a.length, due=a.due, done=a.due + 0.1,
            status="ok", hidden=h,
            first_token=int(np.argmax(reference.head_logits(m, w, h[-1])))))
    return reqs, toks


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_the_check(tiny, seed):
    m, w, limits = tiny
    reqs, toks = served(m, w, seed)
    sound = bench.check(reference, m, w, reqs, toks, limits)
    assert sound["correct"] is True
    assert sound["checks"]["hidden_err"]["value"] == 0.0
    assert sound["checks"]["head_gap_max"]["value"] == 0.0
    out, _ = control_verdict(reference, m, w, reqs, toks, limits)
    assert out["correct"] is False
    assert out["compared"] == len(reqs)
    assert out["checks"]["hidden_err"]["value"] > 3 * limits["hidden_err"]
    # the requests the control was given are left as they were
    assert all(r.err_sq == 0.0 and r.head_gap == 0.0 for r in reqs)


def test_reference_at_last_position_reads_every_prompt_length(tiny):
    m, w, _ = tiny
    for n in (8, 9, 33, 64):
        toks = np.arange(n, dtype=np.int32) % m["vocab_size"]
        h = reference.hidden_states(m, w, toks)
        assert h.shape == (n, m["d_model"])
        last = reference.head_logits(m, w, h[-1])
        assert np.isfinite(last).all() and last.shape == (m["vocab_size"],)
        ctl = reference.head_logits(
            m, w, reference.hidden_states(m, w, toks, control=True)[-1],
            control=True)
        assert 0 < np.abs(ctl - last).max() < np.abs(last).max()
