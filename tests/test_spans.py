"""The served path's profiler spans and copy counters, read from a real
profiler trace (`jax.profiler.ProfileData`) of one tiny request served
through `ExecutorEngine`."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import spans
from repro.core.engine import ExecutorEngine
from repro.core.executor import DisaggregatedExecutor
from repro.core.scheduler import LengthAwareBatcher
from repro.core.trace import Request, TraceClock
from repro.models.lm import init_lm_params

SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
N, S, L, K, E = 11, 16, 2, 2, 2  # prompt, its bucket, layers, top-k, MoE devices


@pytest.fixture(scope="module", params=[False, True],
                ids=["prefill", "emit_kv"])
def served(request, tmp_path_factory):
    """One prompt of N tokens (padded to S) through one attention group and
    E MoE devices with the profiler on, with and without the per-layer KV
    export; the host plane's events per thread line."""
    cfg = get_config("qwen3_moe_235b_a22b").smoke().replace(
        num_layers=L, num_experts=8, top_k=K, dtype=jnp.bfloat16)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    ex = DisaggregatedExecutor(params, cfg, D=1, E=E, moe_kernel="ref",
                               emit_kv=request.param)
    ex.prewarm_buckets(S)
    ex.prewarm_batches([(1, S)])
    eng = ExecutorEngine(ex, clock=TraceClock(), batcher=LengthAwareBatcher(
        inflection=1, max_tokens=1 << 30, exclusive_cutoff=1 << 30,
        max_wait=0.0))
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        eng.submit(Request(rid=0, arrival=0.0, length=N))
        (res,) = eng.drain(timeout=120)
    finally:
        jax.profiler.stop_trace()
        eng.close()
    assert res.status == "ok"
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    lines = [[(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
             for line in host.lines]
    return cfg, ex, lines


def test_every_span_of_the_table_is_recorded(served):
    _, _, lines = served
    names = {n for line in lines for n, _, _ in line}
    assert set(spans.SPANS) <= names
    assert {n for n in names if n.startswith("asap.")} == set(spans.SPANS)


def test_each_span_lies_on_its_threads_line(served):
    _, _, lines = served
    by_line = [{n for n, _, _ in line if n.startswith("asap.")}
               for line in lines]
    by_line = [s for s in by_line if s]
    group = [s for s in by_line if any(n.startswith("asap.group.")
                                       for n in s)]
    moe = [s for s in by_line if any(n.startswith("asap.moe.") for n in s)]
    admission = [s for s in by_line if "asap.engine.launch" in s]
    # one attention group: its loop's spans and the engine's head (run on
    # completion by the same thread) on one line, and nothing else there
    assert group == [{n for n in spans.SPANS if n.startswith("asap.group.")}
                     | {"asap.engine.head"}]
    assert moe == [{n for n in spans.SPANS if n.startswith("asap.moe.")}] * E
    assert admission == [{"asap.engine.launch"}]
    assert len(by_line) == 1 + E + 1


def test_spans_do_not_nest_on_a_thread(served):
    _, _, lines = served
    for line in lines:
        sp = sorted((s, e) for n, s, e in line if n.startswith("asap."))
        assert all(a[1] <= b[0] for a, b in zip(sp, sp[1:]))


def test_the_jitted_steps_have_stable_names(served):
    _, _, lines = served
    names = {n for line in lines for n, _, _ in line}
    for step in ("asap_attn_step", "asap_moe_step", "asap_combine_step"):
        assert f"PjitFunction({step})" in names


def test_every_span_opened_in_src_is_in_the_table():
    opened = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            text = f.read()
        opened |= set(re.findall(r'spans\.span\("([^"]+)"\)', text))
        if not path.endswith(os.path.join("core", "spans.py")):
            assert "TraceAnnotation" not in text, path
    assert opened == set(spans.SPANS)


def test_copied_bytes_match_the_shapes(served):
    cfg, ex, _ = served
    d, it = cfg.d_model, np.dtype(cfg.dtype).itemsize
    kv = S * cfg.num_kv_heads * cfg.head_dim * it if ex.emit_kv else 0
    rows = S * K  # every (token, k) assignment is combined
    group_h2d = (S * 4  # tokens
                 + L * (4  # layer id
                        + rows * (d * 4 + 4 + 4)  # f32 outputs, ids, weights
                        + S * d * 4)  # the f32 sum back in
                 + d * it)  # the head's last hidden state
    group_d2h = (L * (2 * S * K * 4  # routing weights and ids
                      + S * d * it  # the dispatch's source
                      + S * d * 4)  # the scatter-add's f32 sum
                 + L * 2 * kv  # the layer's k and v, with emit_kv
                 + S * d * it  # the final hidden states
                 + 4)  # the first token
    slots, launches = ex.moe_launch_slots, ex.moe_launches
    assert launches.sum() > 0
    assert list(ex.h2d_bytes) == [group_h2d,
                                  *(slots * d * it + 4 * launches)]
    assert list(ex.d2h_bytes) == [group_d2h, *(slots * d * 4)]


def test_pad_rows_are_counted_where_they_are_dispatched(served):
    _, ex, _ = served
    assert list(ex.moe_pad_rows) == [(S - N) * K * L]
