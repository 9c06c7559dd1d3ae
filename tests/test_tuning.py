"""Super-kernel block autotuning table (ISSUE 10): schema, registry, and the
numerics/retrace invariants that make a tuned serve safe."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.super_gmm import tuning
from repro.kernels.super_gmm.ops import super_moe_ffn
from repro.models.common import ModelConfig


@pytest.fixture(autouse=True)
def _isolated_registry():
    """Each test gets a clean process-global table registry and restores the
    prior state afterwards (other suites must never see a leftover table)."""
    with tuning._table_lock:
        saved = (tuning._active, tuning._env_checked)
        tuning._active, tuning._env_checked = None, True
    yield
    with tuning._table_lock:
        tuning._active, tuning._env_checked = saved


def test_config_key_canonical():
    assert tuning.config_key(8, 128, 256, np.float32) == "e8_d128_f256_float32"
    assert tuning.config_key(8, 128, 256, jnp.bfloat16) == \
        "e8_d128_f256_bfloat16"
    assert tuning.config_key(4, 64, 32, "float32") == "e4_d64_f32_float32"


def test_put_lookup_exact_bucket_only():
    t = tuning.TuningTable()
    t.put("e8_d128_f256_float32", 16, (16, 64, 128), (16, 128, 64), us=12.5)
    assert t.lookup("e8_d128_f256_float32", 16) == \
        ((16, 64, 128), (16, 128, 64))
    # no nearest-bucket guessing: a blocking tuned for one C may not even
    # divide another
    assert t.lookup("e8_d128_f256_float32", 32) is None
    assert t.lookup("e4_d128_f256_float32", 16) is None


def test_save_load_roundtrip_and_version_gate(tmp_path):
    t = tuning.TuningTable(meta={"platform": "cpu"})
    t.put("e8_d128_f64_float32", 8, (8, 64, 128), (8, 128, 64), us=1.0)
    path = str(tmp_path / "table.json")
    t.save(path)
    loaded = tuning.TuningTable.load(path)
    assert loaded.lookup("e8_d128_f64_float32", 8) == \
        ((8, 64, 128), (8, 128, 64))
    assert loaded.meta["platform"] == "cpu"
    # a future-versioned table must refuse to load, not silently misapply
    with open(path) as f:
        payload = json.load(f)
    payload["version"] = 99
    with open(path, "w") as f:
        json.dump(payload, f)
    with pytest.raises(ValueError, match="re-run"):
        tuning.TuningTable.load(path)


def test_registry_explicit_install_and_env_fallback(tmp_path, monkeypatch):
    t = tuning.TuningTable()
    t.put("e2_d16_f32_float32", 8, (8, 32, 16), (8, 16, 32))
    # explicit install wins
    tuning.set_table(t)
    assert tuning.lookup_blocks(2, 16, 32, np.float32, 8) == \
        ((8, 32, 16), (8, 16, 32))
    assert tuning.lookup_blocks(2, 16, 32, np.float32, 16) is None
    tuning.set_table(None)
    assert tuning.get_table() is None
    # env fallback: honoured lazily once when nothing was installed
    path = str(tmp_path / "env_table.json")
    t.save(path)
    monkeypatch.setenv(tuning.ENV_VAR, path)
    with tuning._table_lock:
        tuning._active, tuning._env_checked = None, False
    assert tuning.get_table() is not None
    assert tuning.lookup_blocks(2, 16, 32, np.float32, 8) == \
        ((8, 32, 16), (8, 16, 32))
    # a broken env path raises instead of silently falling back
    monkeypatch.setenv(tuning.ENV_VAR, str(tmp_path / "missing.json"))
    with tuning._table_lock:
        tuning._active, tuning._env_checked = None, False
    with pytest.raises(FileNotFoundError):
        tuning.get_table()


def test_sweep_space_heuristic_first():
    # power-of-two divisors, descending, capped at the 128-lane width
    assert tuning.block_candidates(128) == [128, 64, 32, 16, 8, 4, 2, 1]
    assert tuning.block_candidates(48) == [16, 8, 4, 2, 1]
    assert tuning.block_candidates(8, cap=4) == [4, 2, 1]
    cands = tuning.candidate_blockings(16, 64, 128)
    # first candidate == today's _pick_blocks heuristic (largest divisors),
    # so a truncated sweep still contains the default blocking
    assert cands[0] == (16, 64, 128)
    assert len(set(cands)) == len(cands)
    assert tuning.candidate_blockings(16, 64, 128, limit=3) == cands[:3]


def test_tuned_blocking_preserves_kernel_numerics():
    """A table hit changes the Pallas grid blocking ONLY — the launch output
    must match the heuristic blocking within float tolerance.  (Not bit-for-
    bit: block_k re-partitions the K reduction, which legitimately reorders
    the accumulation — the same reason a tuned table entry is allowed to
    shift the last few mantissa bits on real hardware.)"""
    rng = np.random.RandomState(0)
    E, C, d, f, L = 2, 8, 16, 32, 2
    experts = {
        "w_gate": jnp.asarray(rng.randn(L, E, d, f), jnp.float32),
        "w_up": jnp.asarray(rng.randn(L, E, d, f), jnp.float32),
        "w_down": jnp.asarray(rng.randn(L, E, f, d), jnp.float32),
    }
    cfg = ModelConfig(name="t", family="moe", vocab_size=8, d_model=d,
                      d_ff=f, num_layers=L, num_heads=2, num_kv_heads=2,
                      head_dim=8, num_experts=E, top_k=2, moe_d_ff=f,
                      dtype=jnp.float32)
    xb = jnp.asarray(rng.randn(E, C, d), jnp.float32)
    lid = jnp.asarray([1], jnp.int32)
    base = np.asarray(super_moe_ffn(lid, experts, xb, cfg))
    t = tuning.TuningTable()
    t.put(tuning.config_key(E, d, f, jnp.float32), C, (4, 8, 8), (2, 4, 16))
    tuning.set_table(t)
    tuned = np.asarray(super_moe_ffn(lid, experts, xb, cfg))
    np.testing.assert_allclose(tuned, base, rtol=1e-4, atol=1e-4)
    # the ref einsum path never consults the table (no Pallas grid to tune)
    ref = np.asarray(super_moe_ffn(lid, experts, xb, cfg, kernel="ref"))
    np.testing.assert_allclose(ref, base, rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_sweep_harness_quick_produces_loadable_table(tmp_path):
    from benchmarks.tune_superkernel import run
    out = str(tmp_path / "sweep.json")
    r = run(quick=True, buckets=[8], out=out)
    loaded = tuning.TuningTable.load(out)
    assert loaded.meta["buckets"] == [8]
    for key, C, up, _, down, _ in r["rows"]:
        got = loaded.lookup(key, int(C))
        assert got is not None and (str(got[0]), str(got[1])) == (up, down)


@pytest.mark.parametrize("meta,refused", [
    ({"platform": "cpu", "interpret": True}, True),
    ({"platform": "tpu", "device_kind": "TPU v4", "interpret": False}, True),
    ({}, True),
    ({"platform": "tpu", "device_kind": "TPU v5 lite", "interpret": False},
     False),
])
def test_tpu_run_refuses_a_table_swept_elsewhere(monkeypatch, meta, refused):
    """A table swept in interpret mode on the CPU (or on another TPU kind)
    never tunes kernels on a TPU run; on the CPU any table installs."""
    t = tuning.TuningTable(meta=meta)
    tuning.set_table(t)  # CPU backend: accepted whatever its provenance

    class _Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    if refused:
        with pytest.raises(ValueError, match="re-run"):
            tuning.set_table(t)
    else:
        tuning.set_table(t)
        assert tuning.get_table() is t
