"""Pad positions stay out of MoE dispatch: a job with `lengths` sends only
its real (token, k) rows to the MoE devices, the combine keeps its prewarmed
shape, and each row's valid prefix still matches the dense reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import ExecutorEngine
from repro.core.executor import BatchJob, DisaggregatedExecutor
from repro.core.scheduler import LengthAwareBatcher
from repro.core.trace import Request, TraceClock
from repro.models.lm import init_lm_params, lm_backbone

L, K, S = 2, 2, 8  # layers, top-k, the padded length of every job


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen3_moe_235b_a22b").smoke().replace(
        num_layers=L, num_experts=8, top_k=K)
    return cfg, init_lm_params(jax.random.PRNGKey(0), cfg)


def _job(cfg, lengths, bid, seed):
    """Zero-padded prompts, as the engine builds them."""
    rng = np.random.RandomState(seed)
    tok = np.zeros((len(lengths), S), np.int32)
    for i, n in enumerate(lengths):
        tok[i, :n] = rng.randint(0, cfg.vocab_size, n)
    return BatchJob(tokens=tok, bid=bid, lengths=list(lengths))


@pytest.mark.parametrize("lengths,window,combine", [
    ([[5, 8], [3, 7]], 0.0, "segsum"),
    ([[5, 8], [3, 7]], 0.02, "segsum"),
    ([[5, 8], [3, 7]], 0.0, "host"),
    ([[1], [2]], 0.0, "segsum"),
    ([[1], [2]], 0.02, "segsum"),
], ids=["mixed", "mixed-batched", "mixed-host", "one-token",
        "one-token-batched"])
def test_pad_rows_skip_dispatch(model, lengths, window, combine):
    """Only real rows launch, every pad row is counted as skipped, and each
    valid prefix matches the dense reference.  A one-token prompt routes to
    at most `top_k` of the 4 MoE devices: the others get empty regions,
    which still complete."""
    cfg, params = model
    ex = DisaggregatedExecutor(params, cfg, D=2, E=4, moe_batch_window=window,
                               combine_path=combine)
    jobs = [_job(cfg, ls, bid, seed=10 + bid)
            for bid, ls in enumerate(lengths)]
    done = ex.run([[j] for j in jobs])
    real = sum(sum(j.lengths) for j in jobs)
    pads = sum(len(j.lengths) * S - sum(j.lengths) for j in jobs)
    assert ex.moe_launch_rows.sum() == real * K * L
    assert ex.moe_pad_rows.sum() == pads * K * L
    dispatched = [ev[-1] for ev in ex.log if ev[0] == "dispatch"]
    assert sum(dispatched) == real * K * L
    if any(sum(j.lengths) * K < ex.E for j in jobs):  # rows < MoE devices
        assert 0 in dispatched
        assert any(ev[0] == "moe" and ev[-1] == 0 for ev in ex.log)
    for j in done:
        ref, _ = lm_backbone(params, cfg, jnp.asarray(j.tokens),
                             moe_mode="dense")
        for i, n in enumerate(j.lengths):
            np.testing.assert_allclose(np.asarray(j.result)[i, :n],
                                       np.asarray(ref)[i, :n],
                                       rtol=5e-5, atol=5e-5)


def test_no_compile_in_the_window_across_lengths(model):
    """After the prewarm, prompts of several lengths inside one bucket
    compile nothing: the MoE launches land in prewarmed capacity buckets
    and the combine keeps its B·S·top_k rows however many were real."""
    cfg, params = model
    bucket = 16
    ex = DisaggregatedExecutor(params, cfg, D=1, E=4)
    ex.prewarm_buckets(bucket)
    ex.prewarm_batches([(1, bucket)])
    warm = {k: ex.trace_counts[k] for k in ("attn", "moe", "combine")}
    assert all(warm.values())
    eng = ExecutorEngine(ex, clock=TraceClock(), batcher=LengthAwareBatcher(
        inflection=1, max_tokens=1 << 30, exclusive_cutoff=1 << 30,
        max_wait=0.0))
    try:
        for rid, n in enumerate([9, 16, 12, 10]):
            eng.submit(Request(rid=rid, arrival=0.0, length=n))
        res = eng.drain(timeout=120)
    finally:
        eng.close()
    assert sorted(r.status for r in res) == ["ok"] * 4
    assert {k: ex.trace_counts[k] for k in warm} == warm
    assert ex.moe_pad_rows.sum() == (4 * bucket - 47) * K * L
