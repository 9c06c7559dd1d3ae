import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh) cell
on the production mesh (16x16 single-pod, 2x16x16 multi-pod) and extract the
roofline terms from the compiled artifact.

MUST be run as its own process (the XLA_FLAGS line above executes before any
jax import — do not import this module from tests).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_moe_235b_a22b \
      --shape train_4k [--multi-pod] [--out results.jsonl]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--out results.jsonl]
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs import SHAPES, cells, cell_supported, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch import sharding as SH
from repro.launch.hlo_analysis import analyze
from repro.launch.steps import TrainState, build_train_step
from repro.models.api import build_api
from repro.models.common import ModelConfig
from repro.optim.adamw import AdamW

# TPU v5e roofline constants (see DESIGN.md §6 / core/cost_model.py)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def estimate_params(cfg: ModelConfig) -> tuple:
    """(total, active) parameter counts from an eval_shape of init."""
    api = build_api(cfg)
    tree = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        names = "/".join(SH._path_names(path))
        size = 1
        for d in leaf.shape:
            size *= d
        total += size
        if "experts" in names and cfg.num_experts:
            size = size * cfg.top_k // cfg.num_experts
        active += size
    return total, active


def _apply_opts(cfg: ModelConfig, opts: dict, mesh) -> ModelConfig:
    """§Perf knobs: config flags + the pshard logical-axis rules they need."""
    from repro.models import pshard
    pshard.clear_rules()
    if not opts:
        return cfg
    cfg = cfg.replace(**opts)
    rules = {}
    if cfg.attn_dp_constraint:
        rules["batch"] = ("pod", "data") if "pod" in mesh.axis_names \
            else ("data",)
    if cfg.moe_shard_constraints:
        rules.update(moe_group="data", experts="model", moe_rows="data",
                     moe_tokens=("data",))
    if rules:
        pshard.set_rules(**rules)
    return cfg


def build_cell(arch: str, shape_name: str, mesh, opts: Optional[dict] = None):
    """Returns (fn, args_sds, in_shardings, meta)."""
    opts = dict(opts or {})
    accum = int(opts.pop("accum_steps", 1))  # launcher knob, not a cfg field
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    dp = SH._dp_size(mesh)
    B, S = shape.global_batch, shape.seq_len
    if cfg.num_experts:
        tokens = (B // accum) * S if shape.kind == "train" else B
        cfg = cfg.replace(dispatch_groups=SH.dispatch_groups_for(mesh, tokens))
    cfg = _apply_opts(cfg, opts, mesh)
    api = build_api(cfg)
    params_sds = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    pspecs = SH.param_specs(params_sds, cfg, mesh)

    if shape.kind == "train":
        opt = AdamW()
        state_sds = jax.eval_shape(
            lambda: TrainState(api.init(jax.random.PRNGKey(0)),
                               opt.init(params_sds)))
        # opt moments shard like their params; step counter replicated
        from jax.sharding import PartitionSpec as P
        sspecs = TrainState(pspecs, type(state_sds.opt)(P(), pspecs, pspecs))
        batch_sds = jax.eval_shape(
            lambda: api.make_batch(jax.random.PRNGKey(0), S, B, "train"))
        bspecs = SH.batch_specs(batch_sds, mesh)
        fn = build_train_step(api, opt, accum_steps=accum)
        args = (state_sds, batch_sds)
        in_sh = (sspecs, bspecs)
        toks = B * S
    elif shape.kind == "prefill":
        batch_sds = jax.eval_shape(
            lambda: api.make_batch(jax.random.PRNGKey(0), S, B, "prefill"))
        bspecs = SH.batch_specs(batch_sds, mesh)
        fn = lambda params, batch: api.prefill(params, batch)
        args = (params_sds, batch_sds)
        in_sh = (pspecs, bspecs)
        toks = B * S
    else:  # decode
        caches_sds = jax.eval_shape(lambda: api.make_caches(B, S, S - 1))
        cspecs = SH.cache_specs(caches_sds, cfg, B, mesh)
        batch_sds = jax.eval_shape(
            lambda: api.make_batch(jax.random.PRNGKey(0), S, B, "decode"))
        bspecs = SH.batch_specs(batch_sds, mesh)
        fn = lambda params, caches, batch: api.decode(params, caches, batch)
        args = (params_sds, caches_sds, batch_sds)
        in_sh = (pspecs, cspecs, bspecs)
        toks = B
    return cfg, fn, args, in_sh, dict(tokens=toks, kind=shape.kind)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opts: Optional[dict] = None) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = dict(arch=arch, shape=shape_name,
               mesh="2x16x16" if multi_pod else "16x16",
               chips=512 if multi_pod else 256, opts=opts or {})
    ok, why = cell_supported(arch, shape_name)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    try:
        cfg, fn, args, in_sh, meta = build_cell(arch, shape_name, mesh, opts)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        # Static HLO analysis with loop-trip multipliers (cost_analysis counts
        # while bodies once — verified; see launch/hlo_analysis.py).
        hc = analyze(hlo)
        flops = hc.dot_flops
        bytes_accessed = hc.memory_bytes
        cbytes = hc.collective_bytes
        compute_s = flops / PEAK_FLOPS
        memory_s = bytes_accessed / HBM_BW
        collective_s = cbytes / LINK_BW
        total, active = estimate_params(cfg)
        tokens = meta["tokens"]
        mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[meta["kind"]]
        mflops = mult * active * tokens / rec["chips"]
        rec.update(
            status="ok",
            kind=meta["kind"],
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            flops_per_device=flops, bytes_per_device=bytes_accessed,
            collective_bytes_per_device=cbytes,
            collective_by_op=hc.collective_by_op,
            collective_counts=hc.collective_counts,
            xla_cost_flops=float(cost.get("flops", 0.0)),
            xla_bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
            dominant=max([("compute", compute_s), ("memory", memory_s),
                          ("collective", collective_s)], key=lambda kv: kv[1])[0],
            model_flops_per_device=mflops,
            useful_flops_ratio=(mflops / flops) if flops else None,
            params_total=total, params_active=active,
            mem=dict(argument_mb=mem.argument_size_in_bytes / 1e6,
                     output_mb=mem.output_size_in_bytes / 1e6,
                     temp_mb=mem.temp_size_in_bytes / 1e6,
                     alias_mb=mem.alias_size_in_bytes / 1e6,
                     peak_hbm_gb=(mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes) / 1e9),
        )
    except Exception as e:  # record failures — they are bugs to fix
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-extra", action="store_true",
                    help="also run the paper's deepseek_v32 config")
    ap.add_argument("--opts", default="",
                    help="comma list of perf knobs, e.g. "
                         "attn_dp_constraint,inner_remat,moe_shard_constraints"
                         ",gqa_grouped or key=value (remat_policy=...)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    opts = {}
    for item in args.opts.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" in item:
            k, v = item.split("=", 1)
            if v.lower() in ("true", "false"):
                opts[k] = v.lower() == "true"
            else:
                try:
                    opts[k] = int(v)
                except ValueError:
                    opts[k] = v
        else:
            opts[item] = True

    if args.all:
        todo = [(a, s, mp) for (a, s) in cells(include_extra=args.include_extra)
                for mp in (False, True)]
    else:
        meshes = [True] if args.multi_pod else ([False] if args.single_pod
                                                else [False, True])
        todo = [(args.arch, args.shape, mp) for mp in meshes]

    for arch, shape, mp in todo:
        rec = run_cell(arch, shape, mp, opts=opts)
        line = json.dumps(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        brief = {k: rec.get(k) for k in
                 ("arch", "shape", "mesh", "status", "dominant", "compile_s",
                  "wall_s")}
        if rec.get("status") == "ok":
            brief["peak_hbm_gb"] = round(rec["mem"]["peak_hbm_gb"], 2)
        else:
            brief["error"] = rec.get("error", rec.get("reason"))
        print(json.dumps(brief), flush=True)


if __name__ == "__main__":
    main()
