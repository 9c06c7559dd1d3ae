"""The manifest loader finds cells, configurations, mixes and readers by
name, from files it was not edited to know, and refuses bad names."""
import json
import os
import shutil

import pytest

from chipbench import manifest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def test_the_repository_manifest_loads_whole():
    man = manifest.load_manifest(ROOT)
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"], ROOT)
        assert cell.chips == 1
        assert cell.traffic["arrivals"]["rate_rps"] > 0
        for m in cell.end_to_end + cell.per_layer:
            assert callable(manifest.load_reader(m["name"], ROOT))
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for c in man["configs"]:
        assert c["file"].startswith("chipbench/configs/")


def test_the_tail_is_read_per_layer_beside_the_median():
    """The TTFT tail is a per-layer reading; the median is the steady
    cell's end-to-end TTFT, and every per-layer metric moves it."""
    import numpy as np

    from chipbench import bench

    cell = manifest.load_cell("qwen3-235b-a22b.mixed-steady", ROOT)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "ttft_p50_ms"]
    tail = [m for m in cell.per_layer if m["name"] == "engine.ttft_ms_p95"]
    assert tail and tail[0]["source"] == "host_clock"
    assert {m["moves"] for m in cell.per_layer} == {"ttft_p50_ms"}
    read = manifest.load_reader("engine.ttft_ms_p95", ROOT)
    due = np.arange(48) * 1.05
    reqs = [bench.Request(rid=i, length=128, due=float(d), done=float(d) + t)
            for i, (d, t) in enumerate(zip(due, np.linspace(0.2, 1.6, 48)))]
    run = bench.Run(model={}, peak={}, setup_s=1.0, requests=reqs,
                    window_s=51.0, counters={}, experts_per_launch=1)
    assert read(run) == pytest.approx(
        np.percentile(np.linspace(0.2, 1.6, 48), 95) * 1e3)
    for r in reqs[-3:]:  # three never came: the 95th lands on a miss
        r.done = None
    assert read(run) is None


CONFIG_FILES = {
    "qwen3-235b-a22b": "chipbench/configs/qwen3-235b-a22b.json",
    # a configuration that names its own reference module
    "tiny-own": "tests/chipbench/fixtures/tiny-own.json",
}


@pytest.mark.parametrize("name", sorted(CONFIG_FILES))
def test_config_model_block_matches_its_source_keys(name):
    with open(os.path.join(ROOT, CONFIG_FILES[name])) as f:
        c = json.load(f)
    for key, src in c["model_from"].items():
        v = c
        for part in src.split("."):
            v = v[part]
        assert float(v) == float(c["model"][key]), (key, src)
    for key in c["reduced"]:
        assert c[key] != c["published"][key]
    assert set(c["correct"]) == {"hidden_err", "head_gap_max"}
    assert all(v > 0 for v in c["correct"].values())


def _copy_root(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def test_new_cell_config_mix_and_metric_are_found_by_name(tmp_path):
    root = _copy_root(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "chipbench/configs/newmodel.json").write_text(
        json.dumps({"model": {"num_layers": 1}, "correct": {"limit": 1.0}}))
    (root / "chipbench/traffic/short-uniform.json").write_text(json.dumps(
        {"arrivals": {"process": "poisson", "rate_rps": 3.0},
         "lengths": {"dist": "lognormal", "mean": 128, "sigma": 0.1,
                     "min": 128, "max": 160}, "out_len": 1}))
    (root / "chipbench/metrics/new.thing_ms.py").write_text(
        "def read(run):\n    return 2.0 * run\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "newmodel", "source": "x",
                           "file": "chipbench/configs/newmodel.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "newmodel.short", "config": "newmodel",
                             "traffic": "short-uniform", "chips": 1,
                             "why": "x"})
    man["per_layer"].append({"name": "new.thing_ms", "unit": "ms",
                             "better": "lower", "source": "program_span",
                             "layer": "engine", "moves": "ttft_p50_ms",
                             "workloads": ["newmodel.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = manifest.load_cell("newmodel.short", str(root))
    assert cell.config["correct"]["limit"] == 1.0
    assert cell.traffic["lengths"]["mean"] == 128
    assert [m["name"] for m in cell.per_layer] == ["new.thing_ms"]
    assert [m["name"] for m in cell.metrics(trace=True)] == ["new.thing_ms"]
    assert manifest.load_reader("new.thing_ms", str(root))(21.0) == 42.0
    # no file the benchmark already had was edited, except the manifest
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p


@pytest.mark.parametrize("bad", ["", "has space", "a/b", "a,b", ".dot", "x" * 65,
                                 "muµs"])
def test_bad_names_are_refused(bad):
    with pytest.raises(manifest.ManifestError):
        manifest.check_name(bad, "metric")


@pytest.mark.parametrize("bad", ["", "tokens per second", "µs", "x" * 17])
def test_bad_units_are_refused(bad):
    with pytest.raises(manifest.ManifestError):
        manifest.check_unit(bad, "metric")


@pytest.mark.parametrize("good", ["ms", "tokens/s", "%", "s"])
def test_good_units_pass(good):
    assert manifest.check_unit(good, "metric") == good


def test_manifest_with_a_bad_metric_name_is_refused(tmp_path):
    root = _copy_root(tmp_path)
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["per_layer"][0]["name"] = "queue ms"
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    with pytest.raises(manifest.ManifestError):
        manifest.load_manifest(str(root))
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no.such.cell", ROOT)
