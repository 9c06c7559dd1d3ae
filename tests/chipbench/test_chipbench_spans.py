"""The program's `asap.*` spans against the device's ops: idle time on the
host data path, span times and idle gaps labelled by program span."""
import json

import pytest

from chipbench import bench, manifest, spans, tracing

MS = 1_000_000  # ns
CELL = "qwen3-235b-a22b.mixed-steady"


def synthetic():
    """One device; window 0-150 ms; ops at 10-30, 60-70 and 110-120, so
    the gaps are 0-10, 30-60, 70-110 and 120-150."""
    return {
        "device": {"/device:TPU:0": [
            ["fusion.1 f32[8]", 10 * MS, 20 * MS, "jit_asap_attn_step(1)"],
            ["super_gmm.5 f32[4,8,16]", 60 * MS, 10 * MS,
             "jit_asap_moe_step(2)"],
            ["fusion.2 f32[8]", 110 * MS, 10 * MS,
             "jit_asap_combine_step(3)"],
        ]},
        "host": [
            ["main", "harness.window", 0, 150 * MS],
            # 0-10: only a host event, no program span
            ["group0", "np.asarray(jax.Array)", 0, 8 * MS],
            # wholly on busy device time: no idle share
            ["group0", "asap.group.attn", 12 * MS, 16 * MS],
            # 5 ms of it past the op, inside the harness-labelled gap
            ["group0", "asap.group.dispatch", 25 * MS, 10 * MS],
            # 30-60: the harness span over the middle comes first
            ["main", "harness.submit", 40 * MS, 10 * MS],
            ["admission", "asap.engine.launch", 41 * MS, 8 * MS],
            # 70-110: work spans where they run (two at once from 80 to
            # 90), the wait span around them
            ["group0", "asap.group.combine_wait", 65 * MS, 45 * MS],
            ["moe0", "asap.moe.fetch", 75 * MS, 15 * MS],
            ["moe1", "asap.moe.unpack", 80 * MS, 10 * MS],
            # 120-150: the wait span where it runs, whatever else the host
            # does, then no program span
            ["group0", "asap.group.combine_wait", 115 * MS, 30 * MS],
            ["moe0", "np.asarray(jax.Array)", 120 * MS, 30 * MS],
        ],
    }


def test_idle_time_goes_to_what_covers_it_by_tier():
    # 30-60: dispatch to 35, the submit span 40-50 over the launch span;
    # 70-110: fetch 75-80 alone, 80-90 shared with unpack, the wait around
    idle = dict(spans.idle_by_span(synthetic()))
    assert idle == pytest.approx({
        spans.NO_SPAN: 0.010 + 0.015 + 0.005, "harness.submit": 0.010,
        "asap.group.dispatch": 0.005, "asap.moe.fetch": 0.010,
        "asap.moe.unpack": 0.005, "asap.group.combine_wait": 0.025 + 0.025})
    assert sum(idle.values()) == pytest.approx(0.150 - 0.040)


def test_short_gaps_keep_one_label():
    ev = synthetic()
    ev["device"]["/device:TPU:0"] = [
        ["a", 0, 10 * MS, ""], ["b", 10 * MS + 20_000, 140 * MS - 20_000, ""]]
    assert dict(spans.idle_by_span(ev)) == pytest.approx({
        tracing.SHORT_GAP: 20e-6})


def test_idle_host_path_share_by_hand():
    # idle inside work spans: dispatch 30-35, launch 41-49, fetch and
    # unpack 75-90
    assert spans.idle_host_path_share(synthetic()) == pytest.approx(
        100 * (5 + 8 + 15) / 150)


def test_idle_host_path_share_averages_the_device_planes():
    ev = synthetic()
    ev["device"]["/device:TPU:1"] = []  # idle all window: every work span
    # work on plane 1: 12-35 merged, 41-49, 75-90 = 46 ms; plane 0: 28
    assert spans.idle_host_path_share(ev) == pytest.approx(
        100 * (28 + 46) / 2 / 150)


def test_a_trace_without_program_spans_has_no_share():
    ev = synthetic()
    ev["host"] = [h for h in ev["host"] if not h[1].startswith("asap.")]
    assert spans.idle_host_path_share(ev) is None
    assert dict(spans.idle_by_span(ev)) == pytest.approx({
        spans.NO_SPAN: 0.100, "harness.submit": 0.010})


def test_span_times_are_clipped_to_the_window():
    ev = synthetic()
    ev["host"].append(["moe0", "asap.moe.fetch", 140 * MS, 20 * MS])
    times = dict(spans.span_times(ev))
    assert times["asap.group.combine_wait"] == pytest.approx(0.075)
    assert times["asap.moe.fetch"] == pytest.approx(0.025)
    assert spans.span_times(ev, top=1) == [
        ["asap.group.combine_wait", pytest.approx(0.075)]]


def _run(reduced):
    return bench.Run(model={}, peak={}, setup_s=1.0, requests=[],
                     window_s=0.15, counters={"moe_launches": 0.0},
                     experts_per_launch=1, reduced=reduced)


def test_the_reader_reads_the_runs_trace(monkeypatch):
    read = manifest.load_reader("device.idle_host_path_share")
    assert read(_run(None)) is None  # no trace
    seen = []
    monkeypatch.setattr(spans, "load", lambda d: seen.append(d) or
                        synthetic())
    assert read(_run({"busy_s": 0.04})) == pytest.approx(100 * 28 / 150)
    assert seen == [bench.TRACE_DIR]
    ev = synthetic()
    ev["host"] = ev["host"][:2]  # the parent program: no asap.* span
    monkeypatch.setattr(spans, "load", lambda d: ev)
    assert read(_run({"busy_s": 0.04})) is None


def test_the_metric_is_in_the_manifest_for_the_cell():
    cell = manifest.load_cell(CELL)
    m = [m for m in cell.per_layer
         if m["name"] == "device.idle_host_path_share"]
    assert m and m[0]["moves"] == "ttft_p50_ms"
    assert m[0]["source"] == "program_span" and m[0]["unit"] == "%"


def test_the_command_prints_the_breakdown(monkeypatch, capsys):
    monkeypatch.setattr(spans, "load", lambda d: synthetic())
    assert spans.main(["unused"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["idle_host_path_share"] == pytest.approx(100 * 28 / 150)
    assert out["program_spans"][0][0] == "asap.group.combine_wait"
    assert dict(out["idle_by_span"])["asap.moe.fetch"] == pytest.approx(0.01)
