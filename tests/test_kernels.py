"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.interpret import resolve_interpret
from repro.kernels.super_gmm.ops import _pick_blocks, make_super_kernel_gmm, \
    super_moe_ffn
from repro.kernels.super_gmm.ref import super_gmm_ref, super_moe_ffn_ref
from repro.kernels.super_gmm.super_gmm import super_gmm
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ops import mha_flash
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.dispatch_combine.ops import (kernel_moe_combine,
                                                kernel_moe_dispatch)
from repro.models.common import ModelConfig
from repro.models.moe import moe_combine, moe_dispatch, router_topk


# ------------------------------------------------------- interpret policy

@pytest.mark.parametrize("backend,flag,want", [
    ("cpu", None, True), ("cpu", False, False), ("cpu", True, True),
    ("tpu", None, False), ("tpu", False, False),
    ("tpu", True, ValueError), ("gpu", None, RuntimeError)])
def test_resolve_interpret(monkeypatch, backend, flag, want):
    """One decision for every Pallas call: interpreted on the CPU, compiled
    on a TPU, never interpreted on a TPU, and no other backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if isinstance(want, bool):
        assert resolve_interpret(flag) is want
    else:
        with pytest.raises(want):
            resolve_interpret(flag)


def test_super_gmm_expert_ids_index_the_shared_stack():
    """Buffers addressed by expert id into a larger [L, n, K, N] stack match
    the oracle — how an MoE device serves its experts with no copy."""
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    w = jax.random.normal(ks[0], (2, 8, 16, 32), jnp.float32)
    x = jax.random.normal(ks[1], (3, 8, 16), jnp.float32)
    ids = jnp.asarray([6, 1, 4], jnp.int32)
    lid = jnp.asarray([1], jnp.int32)
    out = super_gmm(lid, w, x, ids, block_c=8, block_n=16, block_k=8)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(super_gmm_ref(lid, w, x, ids)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out[1]), np.asarray(x[1] @ w[1, 1]), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- super gmm

@pytest.mark.parametrize("L,E,C,K,N", [(3, 4, 16, 32, 64), (2, 2, 128, 128, 256),
                                       (5, 8, 8, 16, 8), (1, 1, 32, 64, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_super_gmm_sweep(L, E, C, K, N, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 2)
    w = jax.random.normal(ks[0], (L, E, K, N), jnp.float32).astype(dtype)
    x = jax.random.normal(ks[1], (E, C, K), jnp.float32).astype(dtype)
    bc, bn, bk = _pick_blocks(C, N, K)
    for lid in (0, L - 1):
        out = super_gmm(jnp.array([lid], jnp.int32), w, x, block_c=bc,
                        block_n=bn, block_k=bk)
        ref = super_gmm_ref(jnp.array(lid), w, x)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol,
                                   atol=tol)


@pytest.mark.parametrize("L,E,C,K,N", [(2, 2, 192, 160, 192),
                                       (1, 3, 24, 48, 96)])
def test_super_gmm_non_power_of_two_dims(L, E, C, K, N):
    """dims that a bare min(block, dim) clamp would misindex (192 vs 128):
    the divisor rounding must pick a dividing block and stay correct."""
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    w = jax.random.normal(ks[0], (L, E, K, N))
    x = jax.random.normal(ks[1], (E, C, K))
    out = super_gmm(jnp.array([L - 1], jnp.int32), w, x,
                    block_c=128, block_n=128, block_k=128)
    ref = super_gmm_ref(jnp.array(L - 1), w, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_floor_to_divisor():
    from repro.kernels.blocking import floor_to_divisor
    assert floor_to_divisor(192, 128) == 96
    assert floor_to_divisor(256, 128) == 128
    assert floor_to_divisor(100, 128) == 100  # block >= dim -> whole dim
    assert floor_to_divisor(97, 64) == 1      # prime dim still launches
    with pytest.raises(ValueError, match="must be positive"):
        floor_to_divisor(0, 128)
    with pytest.raises(ValueError, match="must be positive"):
        floor_to_divisor(128, -1)


def test_super_gmm_layer_is_runtime_data():
    """One jit trace serves every layer id (the layer-oblivious property)."""
    L, E, C, K, N = 4, 2, 16, 16, 16
    w = jax.random.normal(jax.random.PRNGKey(0), (L, E, K, N))
    x = jax.random.normal(jax.random.PRNGKey(1), (E, C, K))
    outs = [super_gmm(jnp.array([l], jnp.int32), w, x, block_c=8, block_n=8,
                      block_k=8) for l in range(L)]
    refs = [super_gmm_ref(jnp.array(l), w, x) for l in range(L)]
    for o, r in zip(outs, refs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=1e-5,
                                   atol=1e-5)
    # distinct layers give distinct results (weights actually indexed)
    assert np.abs(np.asarray(outs[0] - outs[1])).max() > 1e-3


def test_super_moe_ffn_matches_ref():
    cfg = ModelConfig(name="k", family="moe", num_layers=3, d_model=32,
                      num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                      vocab_size=64, num_experts=4, top_k=2, moe_d_ff=48,
                      dtype=jnp.float32)
    L, E, d, f = 3, 4, 32, 48
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 4)
    experts = {"w_gate": jax.random.normal(ks[0], (L, E, d, f)),
               "w_up": jax.random.normal(ks[1], (L, E, d, f)),
               "w_down": jax.random.normal(ks[2], (L, E, f, d))}
    xb = jax.random.normal(ks[3], (E, 16, d))
    from repro.models.common import act_fn
    for lid in range(L):
        out = super_moe_ffn(jnp.array([lid], jnp.int32), experts, xb, cfg)
        ref = super_moe_ffn_ref(jnp.array(lid), experts, xb, act_fn(cfg.act))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                                   atol=2e-4)


def test_super_moe_ffn_ref_kernel_option():
    """kernel="ref" must match the Pallas grid bit-for-bit in fp32."""
    cfg = ModelConfig(name="k", family="moe", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                      vocab_size=64, num_experts=4, top_k=2, moe_d_ff=48,
                      dtype=jnp.float32)
    L, E, d, f = 2, 4, 32, 48
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    experts = {"w_gate": jax.random.normal(ks[0], (L, E, d, f)),
               "w_up": jax.random.normal(ks[1], (L, E, d, f)),
               "w_down": jax.random.normal(ks[2], (L, E, f, d))}
    xb = jax.random.normal(ks[3], (E, 16, d))
    lid = jnp.array([1], jnp.int32)
    out_p = super_moe_ffn(lid, experts, xb, cfg, kernel="pallas")
    out_r = super_moe_ffn(lid, experts, xb, cfg, kernel="ref")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- capacity packing

def test_pack_unpack_capacity_roundtrip():
    from repro.kernels.super_gmm.ops import (pack_capacity, round_capacity,
                                             unpack_capacity)
    rng = np.random.RandomState(0)
    for n, n_experts in [(1, 1), (7, 3), (64, 4), (129, 8)]:
        tokens = rng.randn(n, 16).astype(np.float32)
        eids = rng.randint(0, n_experts, n)
        xb, order, slots, C = pack_capacity(tokens, eids, n_experts)
        counts = np.bincount(eids, minlength=n_experts)
        assert C == round_capacity(counts.max())
        assert xb.shape == (n_experts, C, 16)
        # every row landed in its own expert's buffer, in arrival order
        for e in range(n_experts):
            rows = tokens[eids == e]
            np.testing.assert_array_equal(xb[e, :len(rows)], rows)
            assert not xb[e, len(rows):].any()  # padding stays zero
        # unpack inverts pack exactly (identity FFN)
        out = unpack_capacity(xb, order, slots, n)
        np.testing.assert_array_equal(out, tokens)


def test_pack_capacity_rejects_dropping_capacity():
    from repro.kernels.super_gmm.ops import pack_capacity
    tokens = np.ones((10, 4), np.float32)
    eids = np.zeros(10, np.int64)
    with pytest.raises(AssertionError):
        pack_capacity(tokens, eids, 1, capacity=8)  # 10 rows won't fit


def test_pack_capacity_multi_roundtrip_and_bit_equality():
    """ISSUE 10: packing SEVERAL regions into one shared capacity buffer and
    running one super-kernel launch must be BIT-equal to running each region
    through its own pack -> launch -> unpack.  Every capacity row is an
    independent dot chain, so merging changes WHERE a row sits, never the
    reduction order — checked with a real (ref-kernel) expert FFN, not just
    the identity."""
    from repro.kernels.super_gmm.ops import (pack_capacity, pack_capacity_multi,
                                             unpack_capacity,
                                             unpack_capacity_multi)
    rng = np.random.RandomState(7)
    n_experts, d, f = 4, 16, 32
    L = 2
    experts = {
        "w_gate": jnp.asarray(rng.randn(L, n_experts, d, f), jnp.float32),
        "w_up": jnp.asarray(rng.randn(L, n_experts, d, f), jnp.float32),
        "w_down": jnp.asarray(rng.randn(L, n_experts, f, d), jnp.float32),
    }
    cfg = ModelConfig(name="k", family="moe", vocab_size=8, d_model=d,
                      d_ff=f, num_layers=L, num_heads=2, num_kv_heads=2,
                      head_dim=8, num_experts=n_experts, top_k=2, moe_d_ff=f,
                      dtype=jnp.float32)
    lid = jnp.asarray([1], jnp.int32)

    def ffn(xb):
        return np.asarray(super_moe_ffn(lid, experts, xb.astype(np.float32),
                                        cfg, kernel="ref"))

    sizes = [5, 1, 12, 3]
    token_list = [rng.randn(n, d).astype(np.float32) for n in sizes]
    eid_list = [rng.randint(0, n_experts, n) for n in sizes]

    # merged: one pack, ONE launch, split outputs by provenance bounds
    xb, order, slots, C, bounds = pack_capacity_multi(
        token_list, eid_list, n_experts)
    assert list(bounds) == list(np.cumsum(sizes))
    outs_multi = unpack_capacity_multi(ffn(xb), order, slots, bounds)

    # per-region reference: own pack/launch/unpack each — with the MERGED
    # bucket C so the jitted shape matches, and separately with each
    # region's OWN bucket (the per-region serving path)
    for r, (tokens, eids) in enumerate(zip(token_list, eid_list)):
        for cap in (C, None):
            xb1, o1, s1, _ = pack_capacity(tokens, eids, n_experts,
                                           capacity=cap)
            ref = unpack_capacity(ffn(xb1), o1, s1, len(tokens))
            np.testing.assert_array_equal(outs_multi[r], ref)

    # single-region degenerate case: multi == plain pack
    xb1, o1, s1, C1, b1 = pack_capacity_multi(token_list[:1], eid_list[:1],
                                              n_experts)
    xb2, o2, s2, C2 = pack_capacity(token_list[0], eid_list[0], n_experts)
    np.testing.assert_array_equal(xb1, xb2)
    assert C1 == C2 and list(b1) == [sizes[0]]

    # empty region list is a caller bug, not a silent no-op
    with pytest.raises(AssertionError):
        pack_capacity_multi([], [], n_experts)


def test_round_capacity_buckets():
    from repro.kernels.super_gmm.ops import round_capacity
    assert round_capacity(0) == 8
    assert round_capacity(1) == 8
    assert round_capacity(8) == 8
    assert round_capacity(9) == 16
    assert round_capacity(100) == 128
    # bucketing -> O(log N) distinct shapes for the jit cache
    assert len({round_capacity(n) for n in range(1, 1000)}) <= 8


def test_lm_forward_with_super_kernel_matches_einsum():
    from repro.configs import get_config
    from repro.models.lm import init_lm_params, lm_forward
    cfg = get_config("qwen3_moe_235b_a22b").smoke().replace(
        num_layers=3, num_experts=4, top_k=2, capacity_factor=8.0)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    gmm = make_super_kernel_gmm(params["stages"][0]["ffn"]["experts"], cfg)
    lo_k, _ = lm_forward(params, cfg, tokens, gmm=gmm)
    lo_e, _ = lm_forward(params, cfg, tokens)
    np.testing.assert_allclose(np.asarray(lo_k), np.asarray(lo_e), rtol=2e-3,
                               atol=2e-3)


# ---------------------------------------------------------- flash attention

@pytest.mark.parametrize("BH,S,dh,bq,bk", [(4, 128, 64, 32, 32),
                                           (2, 256, 32, 64, 64),
                                           (1, 64, 128, 16, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(BH, S, dh, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (BH, S, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (BH, S, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (BH, S, dh)).astype(dtype)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_non_power_of_two_seq():
    """S=192 with the default 128 blocks: min-clamp would misindex; the
    divisor rounding (96) must match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (2, 192, 32)) for kk in ks)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("window,softcap", [(64, None), (None, 30.0),
                                            (32, 20.0)])
def test_flash_attention_window_softcap(window, softcap):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (2, 128, 32)) for kk in ks)
    out = flash_attention(q, k, v, window=window, softcap=softcap,
                          block_q=32, block_k=32)
    ref = attention_ref(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_mha_flash_gqa_layout():
    from repro.models.attention import dense_causal_attention
    cfg = ModelConfig(name="k", family="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=64,
                      vocab_size=64, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    out = mha_flash(q, k, v, block_q=32, block_k=32)
    ref = dense_causal_attention(q, k, v, cfg, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


# --------------------------------------------------------- dispatch/combine

@pytest.mark.parametrize("T,E,K", [(64, 8, 2), (128, 4, 4), (32, 16, 1)])
def test_kernel_dispatch_combine_vs_jnp(T, E, K):
    cfg = ModelConfig(name="k", family="moe", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, head_dim=8, d_ff=32,
                      vocab_size=64, num_experts=E, top_k=K, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (T, cfg.d_model))
    router = jax.random.normal(jax.random.PRNGKey(1), (cfg.d_model, E))
    w, idx, _ = router_topk(router, x, cfg)
    xb_k, info_k = kernel_moe_dispatch(x, idx, cfg)
    xb_j, info_j = moe_dispatch(x, idx, cfg)
    np.testing.assert_array_equal(np.asarray(xb_k), np.asarray(xb_j))
    yb = xb_j * 3.0
    y_k = kernel_moe_combine(yb, info_k, w, T)
    y_j = moe_combine(yb, info_j, w, T)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j), rtol=1e-6,
                               atol=1e-6)
