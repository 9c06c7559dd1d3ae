"""FLOP and byte counts of Qwen3-235B-A22B and DBRX against hand counts:
the model's FLOPs from the default reference module, the super-GMM's work
from `counts`."""
import json
import os

import pytest

from chipbench import counts, manifest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

# DBRX (HF databricks/dbrx-base config.json), one layer at published widths
DBRX = {"num_layers": 1, "d_model": 6144, "num_heads": 48, "num_kv_heads": 8,
        "head_dim": 128, "num_experts": 16, "top_k": 4, "expert_d_ff": 10752,
        "vocab_size": 100352, "rope_theta": 500000.0, "norm_eps": 1e-06,
        "qk_norm": False, "router_renorm": True, "dtype": "bfloat16"}


def model(name):
    if name == "dbrx-132b":
        return DBRX
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)["model"]


def prompt_flops(m, n):
    return manifest.load_reference(manifest.DEFAULT_REFERENCE).prompt_flops(
        m, n)


# Prompt of 1000 real tokens.  Per layer: q/k/v/o projections
# 2n d (H hd + 2 KV hd) + 2n H hd d, causal attention 2 * n(n+1)/2 * H hd * 2,
# router 2n d E, experts n top_k 6 d f; the head 2 d V at one position.
QWEN3_1000 = (
    2 * 1000 * 4096 * (64 * 128 + 2 * 4 * 128) + 2 * 1000 * 64 * 128 * 4096
    + 2 * 500500 * 64 * 128 * 2
    + 2 * 1000 * 4096 * 128
    + 1000 * 8 * 6 * 4096 * 1536
    + 2 * 4096 * 151936)
DBRX_1000 = (
    2 * 1000 * 6144 * (48 * 128 + 2 * 8 * 128) + 2 * 1000 * 48 * 128 * 6144
    + 2 * 500500 * 48 * 128 * 2
    + 2 * 1000 * 6144 * 16
    + 1000 * 4 * 6 * 6144 * 10752
    + 2 * 6144 * 100352)


@pytest.mark.parametrize("name,want", [
    ("qwen3-235b-a22b", 463_289_843_712),
    ("dbrx-132b", 1_775_337_701_376),
])
def test_prompt_flops_by_hand(name, want):
    assert want == {"qwen3-235b-a22b": QWEN3_1000, "dbrx-132b": DBRX_1000}[name]
    assert prompt_flops(model(name), 1000) == want


def test_prompt_flops_grow_with_the_prompt():
    m = model("qwen3-235b-a22b")
    one = prompt_flops(m, 1)
    # a prompt of one token: projections, one attention pair, router, 8
    # experts, head
    assert one == (2 * 4096 * 9216 + 2 * 8192 * 4096 + 2 * 64 * 128 * 2
                   + 2 * 4096 * 128 + 8 * 6 * 4096 * 1536 + 2 * 4096 * 151936)
    assert prompt_flops(m, 2048) > 2 * prompt_flops(m, 1024)


@pytest.mark.parametrize("name,pairs,launches,held,flops,nbytes,bound", [
    # 1000 tokens x top-8 over 4 launches of 32 experts each
    ("qwen3-235b-a22b", 8000, 4, 32, 8000 * 6 * 4096 * 1536,
     4 * 32 * 3 * 4096 * 1536 * 2 + 8000 * 2 * 4096 * 2, "memory"),
    # 500 tokens x top-4 over 4 launches of 4 experts each
    ("dbrx-132b", 2000, 4, 4, 2000 * 6 * 6144 * 10752,
     4 * 4 * 3 * 6144 * 10752 * 2 + 2000 * 2 * 6144 * 2, "memory"),
    # 1000 tokens: just past the v5e ridge (8.05 ms of FLOPs, 7.86 of bytes)
    ("dbrx-132b", 4000, 4, 4, 4000 * 6 * 6144 * 10752,
     4 * 4 * 3 * 6144 * 10752 * 2 + 4000 * 2 * 6144 * 2, "compute"),
    # 16k tokens through one DBRX launch: past the ridge
    ("dbrx-132b", 64000, 1, 4, 64000 * 6 * 6144 * 10752,
     4 * 3 * 6144 * 10752 * 2 + 64000 * 2 * 6144 * 2, "compute"),
])
def test_super_gmm_work_by_hand(name, pairs, launches, held, flops, nbytes,
                                bound):
    m = model(name)
    got = counts.super_gmm_work(m, pairs, launches, held)
    assert got == (flops, nbytes)
    peak = counts.peaks("TPU v5 lite")
    t, which = counts.least_time(*got, peak)
    assert which == bound
    assert t == max(flops / 197e12, nbytes / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
