"""The reduction from a profiler trace to busy time, op time and idle gaps."""
import gzip
import json
import os

import pytest

from chipbench import tracing
from chipbench.readers import SUPER_GMM_OPS

# 1.5 s of a `--trace 1` run of qwen3-235b-a22b.mixed-steady on one TPU v5
# lite, as `tracing.extract` gave it, from 5 ms before a super-GMM launch;
# its `harness.window` span is that slice
CHIP_SLICE = os.path.join(os.path.dirname(__file__), "fixtures",
                          "chip-trace-slice.json.gz")

MS = 1_000_000  # ns


def synthetic():
    """One device; window 0-100 ms; ops at 10-30 (two overlapping) and
    60-70; a harness submit span over 40-50; a host event over 75-95."""
    return {
        "device": {"/device:TPU:0": [
            ["fusion.1 f32[8]", 10 * MS, 15 * MS, "jit_step(1)"],
            ["super_gmm.5 f32[4,8,16]", 20 * MS, 10 * MS, "jit_step(1)"],
            ["fusion.1 f32[8]", 60 * MS, 10 * MS, "jit_step(1)"],
            ["copy.9 f32[8]", 150 * MS, 10 * MS, "jit_late(2)"],  # past it
        ]},
        "host": [
            ["main", "harness.window", 0, 100 * MS],
            ["main", "harness.submit", 40 * MS, 10 * MS],
            ["group0", "PjitFunction(step)", 75 * MS, 20 * MS],
            ["group0", "ExecuteHelper", 0, 5 * MS],
        ],
    }


def test_busy_ops_and_gaps_by_hand():
    r = tracing.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.030)  # 10-30 merged, 60-70
    assert r["ops"] == pytest.approx({
        "jit_step(1)/fusion.1 f32[8]": 0.025,
        "jit_step(1)/super_gmm.5 f32[4,8,16]": 0.010})
    assert r["device_ops"][0] == ["jit_step(1)/fusion.1 f32[8]",
                                  pytest.approx(0.025)]
    idle = dict(r["idle_gaps"])
    # 0-10: ExecuteHelper overlaps 5 ms of it; 30-60: the submit span
    # covers its middle (45); 70-100: PjitFunction overlaps most of it
    assert idle == pytest.approx({"ExecuteHelper": 0.010,
                                  "harness.submit": 0.030,
                                  "PjitFunction(step)": 0.030})
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_a_recorded_chip_trace_reduces():
    with gzip.open(CHIP_SLICE, "rt") as f:
        ev = json.load(f)
    r = tracing.reduce(ev, top=10_000)
    assert r["window_s"] == pytest.approx(1.5)
    assert r["busy_s"] == pytest.approx(0.514768986)
    assert sum(v for _, v in r["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["window_s"])
    # the super-GMM's launches keep their HLO name: gate, up and down of
    # each capacity bucket, each keyed by the program that ran it
    gmm = {n: v for n, v in r["ops"].items() if SUPER_GMM_OPS.search(n)}
    assert len(gmm) == 9
    assert {n.split("/")[1].split()[0] for n in gmm} == {
        "super_gmm.3", "super_gmm.4", "super_gmm.5"}
    assert sum(gmm.values()) == pytest.approx(0.496941097)
    assert SUPER_GMM_OPS.search(r["device_ops"][0][0])
    assert r["idle_gaps"][0][0] == "np.asarray(jax.Array)"


def test_short_gaps_are_summed_under_one_label():
    ev = synthetic()
    ev["device"]["/device:TPU:0"] = [
        ["a", 0, 10 * MS, ""],
        ["b", 10 * MS + 20_000, 90 * MS - 20_000, ""]]
    r = tracing.reduce(ev)
    assert dict(r["idle_gaps"]) == pytest.approx({tracing.SHORT_GAP: 20e-6})


@pytest.mark.parametrize("hlo,name", [
    ("%super_gmm.5 = f32[32,2048,4096]{2,1,0:T(8,128)} custom-call(s32[1]"
     "{0:T(128)S(6)} %copy), custom_call_target=\"tpu_custom_call\"",
     "super_gmm.5 f32[32,2048,4096]"),
    ("%copy-start = (s32[1,256]{1,0:T(1,128)S(1)}, u32[]{:S(2)}) "
     "copy-start(s32[1,256]{1,0:T(1,128)} %indices.1)",
     "copy-start (s32[1,256], u32[])"),
    ("ExecuteHelper", "ExecuteHelper"),
])
def test_op_names_from_hlo_text(hlo, name):
    assert tracing.op_name(hlo) == name


def test_union_and_clip():
    assert tracing.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4),
                                                               (5, 10)]
    assert tracing.clip([(0, 4), (5, 10)], 2, 7) == [(2, 4), (5, 7)]


def test_a_trace_without_a_window_or_device_ops_is_refused():
    ev = synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tracing.reduce(ev)
    ev = synthetic()
    ev["device"] = {}
    with pytest.raises(ValueError):
        tracing.reduce(ev)
