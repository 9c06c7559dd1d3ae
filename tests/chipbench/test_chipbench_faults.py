"""`correct` comes out false when the timed path is broken underneath.

Each case drives the rest of a run (schedule, weights from the seed, the
server prewarmed, the open-loop window, the drain, the float32 reference
check and the metric readers) at a test size on the CPU, past the
harness's look for a chip.  The faults the prefill cells can have:

  token     the served token altered where it is produced (the engine's
            head returns negated logits, so it serves the least likely)
  expert    one of the four MoE devices runs its rows through a
            neighbour's expert weights
  stuck     the executor's final norm is skipped, so the served token is
            read off the un-normalised residual (a step whose output is
            left as its input)

One request per batch and one chip: no batch half to leave out, and no
exchange between chips to leave out.
"""
import json
import os
import shutil
import time

import jax.numpy as jnp
import pytest

from chipbench import bench, counts, manifest

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout holding the benchmark and one test-size cell."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench")
    shutil.copy(os.path.join(HERE, "fixtures", "tiny.json"),
                root / "chipbench/configs/tiny.json")
    shutil.copy(os.path.join(HERE, "fixtures", "tiny-mix.json"),
                root / "chipbench/traffic/tiny-mix.json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                       "file": "chipbench/configs/tiny.json", "why": "test"}]
    man["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                         "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for m in man["end_to_end"] + man["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return str(root)


def _run(root, monkeypatch, seed=20240611):
    real = counts.peaks
    # the CPU has no row in the peaks table; lend it the v5e's
    monkeypatch.setattr(counts, "peaks", lambda kind: real("TPU v5 lite"))
    cell = manifest.load_cell("tiny.mix", root)
    return bench.measure(cell, seed, 2.0, False, time.perf_counter())


def test_sound_run_is_correct(tiny_root, monkeypatch):
    out = _run(tiny_root, monkeypatch)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 16
    assert set(out["metrics"]) == {"setup_s", "ttft_p50_ms"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["hidden_err"]["value"] <= 1e-3
    assert out["checks"]["head_gap_max"]["value"] <= 1e-3


def _token_altered(monkeypatch):
    from repro.core import engine

    real = engine.lm_head
    monkeypatch.setattr(engine, "lm_head",
                        lambda p, h, cfg: -real(p, h, cfg))


def _expert_swapped(monkeypatch):
    from repro.core import executor

    real = executor.DisaggregatedExecutor.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        self._moe_ids[0] = jnp.roll(self._moe_ids[0], 1)

    monkeypatch.setattr(executor.DisaggregatedExecutor, "__init__", init)


def _norm_skipped(monkeypatch):
    from repro.core import executor

    real = executor.apply_norm

    def norm(x, w, cfg):
        return x if w.shape == (cfg.d_model,) and x.ndim == 3 \
            and w is _final[0] else real(x, w, cfg)

    _final = []
    real_init = executor.DisaggregatedExecutor.__init__

    def init(self, params, *a, **kw):
        _final[:] = [params["final_norm"]]
        real_init(self, params, *a, **kw)

    monkeypatch.setattr(executor, "apply_norm", norm)
    monkeypatch.setattr(executor.DisaggregatedExecutor, "__init__", init)


@pytest.mark.parametrize("plant", [_token_altered, _expert_swapped,
                                   _norm_skipped],
                         ids=["token", "expert", "stuck"])
def test_fault_is_not_correct(tiny_root, monkeypatch, plant):
    plant(monkeypatch)
    out = _run(tiny_root, monkeypatch)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
