"""The served path's jitted steps take their weights as arguments, and the
executor's prewarm compiles exactly the shapes and dtypes serving uses."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decode import DecodeExecutor
from repro.core.executor import BatchJob, DisaggregatedExecutor
from repro.launch.serve import init_params, model_config
from repro.models.lm import embed_tokens


def _jit_const_sizes(closed) -> list:
    """Sizes of every constant baked into a jit nested in `closed` — a
    weight array the jitted step closed over shows up here.  (The top-level
    consts are `make_jaxpr`'s own capture of the arrays a call passes.)"""
    sizes = []
    for eqn in closed.jaxpr.eqns:
        for p in eqn.params.values():
            if hasattr(p, "consts") and hasattr(p, "jaxpr"):
                sizes += [int(np.size(c)) for c in p.consts]
                sizes += _jit_const_sizes(p)
    return sizes


@pytest.fixture(scope="module")
def served():
    cfg = model_config("smoke").replace(dtype=jnp.bfloat16)
    return cfg, init_params(cfg, 0)


def test_steps_take_weights_as_arguments(served):
    cfg, params = served
    ex = DisaggregatedExecutor(params, cfg, D=1, E=2, emit_kv=True)
    h = embed_tokens(params, jnp.zeros((1, 8), jnp.int32), None, cfg)
    n_e = len(ex.dev_experts[0])
    assert n_e and ex._moe_ids[0] is not None
    rt = DecodeExecutor(params, cfg, slots=2, max_len=16)
    xb = np.zeros((n_e, 8, cfg.d_model), cfg.dtype)
    jaxprs = {
        "attention": jax.make_jaxpr(ex._attn_step)(
            jnp.asarray(0, jnp.int32), h),
        "moe": jax.make_jaxpr(lambda: ex._moe_launch(0, 0, xb))(),
        "decode": jax.make_jaxpr(lambda: rt._step(
            rt.params, rt._k, rt._v, rt._tokens, rt._lengths,
            jnp.asarray(rt._active)))(),
    }
    smallest_weight = min(int(np.size(a)) for a in jax.tree.leaves(params))
    for name, closed in jaxprs.items():
        assert max(_jit_const_sizes(closed), default=0) < smallest_weight, \
            name


def test_prewarm_compiles_every_serving_shape(served):
    """Prewarm in the model's dtype (bf16 here): serving afterwards traces
    nothing new — no attention, combine or MoE-bucket compile mid-run."""
    cfg, params = served
    ex = DisaggregatedExecutor(params, cfg, D=2, E=2)
    S = 8
    ex.prewarm_buckets(S * cfg.top_k)
    ex.prewarm_batches([(1, S)])
    warm = dict(ex.trace_counts)
    assert warm["attn"] == 1 and warm["combine"] == 1 and warm["moe"] >= 1
    jobs = [BatchJob(tokens=np.random.RandomState(i).randint(
        0, cfg.vocab_size, (1, S)), bid=i) for i in range(4)]
    done = ex.run([jobs[:2], jobs[2:]])
    assert all(j.result is not None for j in done)
    assert dict(ex.trace_counts) == warm
    assert ex.bucket_misses.sum() == 0


def test_decode_prewarm_is_the_only_trace(served):
    cfg, params = served
    rt = DecodeExecutor(params, cfg, slots=2, max_len=16)
    rt.prewarm()
    assert rt.trace_counts["decode_step"] == 1
    assert not rt._active.any() and int(np.asarray(rt._lengths).sum()) == 0
