"""HLO cost contracts — a perf-regression tripwire that needs no TPU.

For a small pinned set of (arch, step) cells, compile the real step on a
forced-8-device host mesh (2 data x 4 model), run `launch.hlo_analysis`
over the compiled HLO, and diff dot-FLOPs / collective-bytes / memory-bytes
against checked-in golden JSON with a relative tolerance band.  A change
that silently inflates communication volume or FLOPs (a dropped sharding
rule, an accidental all-gather, a duplicated matmul) fails CI here — years
before a TPU run would have noticed.

The numbers are DETERMINISTIC for a pinned jax version + mesh shape: the
gate compares exact analysis of the compiled artifact, not wall-clock.

Workflow (see docs/static_analysis.md):
    python -m repro.analysis --contracts              # verify
    python -m repro.analysis --update-contracts       # re-baseline
The CLI sets XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax
is imported; this module must NOT import jax at module level (the flag has
to land first), which is also why the tests drive it via subprocess.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "contracts_golden")

#: relative tolerance band: |measured - golden| / golden must stay under
#: this for every metric.  Tight enough to catch a duplicated collective
#: (+100%) or an un-sharded matmul; loose enough for minor jax-version
#: fusion jitter.
RTOL = 0.02

MESH_SHAPE = (2, 4)  # (data, model) over 8 forced host devices
MESH_AXES = ("data", "model")

METRICS = ("dot_flops", "collective_bytes", "memory_bytes")


@dataclasses.dataclass(frozen=True)
class ContractSpec:
    name: str
    arch: str
    kind: str  # "train" | "prefill"
    batch: int = 8
    seq: int = 64
    layers: int = 2


#: the pinned contract cells: the MoE prefill path (the paper's subject),
#: the MoE train path (adds the optimizer + gradient collectives), and a
#: dense control (catches regressions that MoE noise could mask).
CONTRACTS = (
    ContractSpec("moe_train", "qwen3_moe_235b_a22b", "train"),
    ContractSpec("moe_prefill", "qwen3_moe_235b_a22b", "prefill"),
    ContractSpec("dense_train", "gemma3_1b", "train"),
)


def golden_path(name: str) -> str:
    return os.path.join(GOLDEN_DIR, f"{name}.json")


def load_golden(name: str) -> Optional[dict]:
    path = golden_path(name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def save_golden(name: str, record: dict):
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    with open(golden_path(name), "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")


def diff_metrics(golden: Dict[str, float], measured: Dict[str, float],
                 rtol: float = RTOL) -> List[dict]:
    """Violations of the tolerance band (pure function — unit-testable
    without compiling anything).  Both directions fail: inflation is a
    regression, deflation means the golden is stale — re-baseline
    deliberately with --update-contracts."""
    out = []
    for metric in METRICS:
        g, m = golden.get(metric), measured.get(metric)
        if g is None or m is None:
            out.append(dict(metric=metric, golden=g, measured=m,
                            rel=None, why="metric missing"))
            continue
        rel = (m - g) / g if g else (0.0 if m == g else float("inf"))
        if abs(rel) > rtol:
            why = "inflated" if rel > 0 else "deflated"
            out.append(dict(metric=metric, golden=g, measured=m,
                            rel=round(rel, 6), why=why))
    return out


# ---------------------------------------------------------------------------
# measurement (lazy jax)
# ---------------------------------------------------------------------------


def _make_mesh():
    import jax
    from jax.sharding import AxisType
    n = len(jax.devices())
    need = MESH_SHAPE[0] * MESH_SHAPE[1]
    if n < need:
        raise RuntimeError(
            f"HLO contracts need {need} host devices but jax sees {n} — "
            f"run via `python -m repro.analysis --contracts` (it sets "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before jax is imported)")
    return jax.make_mesh(MESH_SHAPE, MESH_AXES,
                         axis_types=(AxisType.Auto,) * len(MESH_AXES))


def measure(spec: ContractSpec, mesh=None) -> Dict[str, float]:
    """Compile the contract cell and return its hlo_analysis metrics."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.launch import sharding as SH
    from repro.launch.hlo_analysis import analyze
    from repro.launch.steps import TrainState, build_train_step
    from repro.models.api import build_api
    from repro.optim.adamw import AdamW

    if mesh is None:
        mesh = _make_mesh()
    B, S = spec.batch, spec.seq
    cfg = get_config(spec.arch).smoke().replace(num_layers=spec.layers)
    if cfg.num_experts:
        tokens = B * S if spec.kind == "train" else B
        cfg = cfg.replace(
            num_experts=4, top_k=2,
            dispatch_groups=SH.dispatch_groups_for(mesh, tokens))
    api = build_api(cfg)
    key = jax.random.PRNGKey(0)
    params_sds = jax.eval_shape(lambda: api.init(key))
    pspecs = SH.param_specs(params_sds, cfg, mesh)
    batch_sds = jax.eval_shape(lambda: api.make_batch(key, S, B, spec.kind))
    bspecs = SH.batch_specs(batch_sds, mesh)
    if spec.kind == "train":
        opt = AdamW()
        state_sds = jax.eval_shape(
            lambda: TrainState(api.init(key), opt.init(params_sds)))
        sspecs = TrainState(pspecs, type(state_sds.opt)(P(), pspecs, pspecs))
        fn = build_train_step(api, opt)
        args, in_sh = (state_sds, batch_sds), (sspecs, bspecs)
    elif spec.kind == "prefill":
        def fn(params, batch):
            return api.prefill(params, batch)
        args, in_sh = (params_sds, batch_sds), (pspecs, bspecs)
    else:
        raise ValueError(f"unknown contract kind {spec.kind!r}")
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        hlo = compiled.as_text()
    hc = analyze(hlo)
    return {
        "dot_flops": float(hc.dot_flops),
        "collective_bytes": float(hc.collective_bytes),
        "memory_bytes": float(hc.memory_bytes),
        "collective_by_op": {k: float(v)
                             for k, v in hc.collective_by_op.items() if v},
    }


def run_contracts(update: bool = False,
                  rtol: float = RTOL) -> Tuple[bool, dict]:
    """Verify (or re-baseline) every pinned contract.

    Returns (ok, report); report["contracts"] holds one entry per cell with
    status "ok" | "fail" | "missing-golden" | "updated"."""
    mesh = _make_mesh()
    entries = []
    ok = True
    for spec in CONTRACTS:
        measured = measure(spec, mesh)
        entry = dict(name=spec.name, arch=spec.arch, kind=spec.kind,
                     mesh=list(MESH_SHAPE), measured=measured)
        if update:
            save_golden(spec.name, dict(
                name=spec.name, arch=spec.arch, kind=spec.kind,
                batch=spec.batch, seq=spec.seq, layers=spec.layers,
                mesh=list(MESH_SHAPE), rtol=rtol,
                metrics={k: measured[k] for k in METRICS}))
            entry.update(status="updated")
        else:
            golden = load_golden(spec.name)
            if golden is None:
                entry.update(status="missing-golden",
                             why=f"no golden at {golden_path(spec.name)} — "
                                 f"run --update-contracts")
                ok = False
            else:
                violations = diff_metrics(golden["metrics"], measured,
                                          rtol=golden.get("rtol", rtol))
                entry.update(status="fail" if violations else "ok",
                             golden=golden["metrics"],
                             violations=violations)
                ok = ok and not violations
        entries.append(entry)
    return ok, {"ok": ok, "rtol": rtol, "contracts": entries}
