import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before ANY other import: jax locks the
# device count at first init, and the multi-pod dry-run needs 512
# placeholder host devices to build the production mesh. Do not set this
# anywhere global — smoke tests and benches must see 1 device.

"""Multi-pod dry-run (deliverable e): for every (architecture x input-shape
x mesh) cell, `.lower().compile()` the sharded step on the production mesh
and record memory/cost/collective analyses for EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out experiments/dryrun

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system; skipped cells (long_500k on full-attention archs)
are recorded with their DESIGN.md §5 rationale.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import (SHAPES, cell_is_runnable, get_config,
                                list_archs)
from repro.dist import sharding as SH
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.models import flops as FL
from repro.models import model as M
from repro.models.params import abstract_from_template
from repro.train.optimizer import AdamW, AdamWState
from repro.train import step as STEP


def abstract_opt_state(tmpl, master=True):
    f32 = abstract_from_template(tmpl, jnp.float32)
    return AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=f32,
        nu=abstract_from_template(tmpl, jnp.float32),
        master=abstract_from_template(tmpl, jnp.float32) if master else None,
    )


# Gradient-accumulation microbatch count per train cell: the memory knob
# for big-activation architectures (chosen so live bytes < 16GB; see
# EXPERIMENTS.md §Perf iteration 4). Default 1.
MICROBATCHES = {
    "mixtral-8x7b": 4,
    "qwen2-moe-a2.7b": 4,
    "llama-3.2-vision-11b": 4,
    "zamba2-2.7b": 4,
    "starcoder2-7b": 2,
    "whisper-large-v3": 2,
    "granite-8b": 2,
    "h2o-danube-3-4b": 2,
}


# Named sharding variants for the §Perf hillclimb. "flat_dp" retires TP
# entirely: both mesh axes do DP/FSDP (for small models whose TP activation
# all-reduces dominate); "ep" maps the expert dim onto the model axis.
VARIANTS = {
    "baseline": {},
    "flat_dp": {
        "param": {"heads": None, "kv_heads": None, "mlp": None, "vocab": None,
                  "inner": None, "embed": ("data", "model"),
                  "expert_mlp": ("data", "model"), "expert_embed": None},
        "act": {"batch": ("pod", "data", "model"), "seq": None, "heads": None,
                "kv_heads": None, "mlp": None, "vocab": None, "inner": None,
                "head_dim": None, "tokens": ("pod", "data", "model")},
    },
    "ep": {
        "param": {"experts": "model", "expert_mlp": ("data",), "expert_embed": None},
        "act": {},
    },
    # serving: weights TP-only resident (no FSDP), so decode steps carry no
    # per-step parameter all-gathers
    "serve_tp": {
        "param": {"embed": None, "expert_embed": None},
        "act": {},
    },
    # training: remat policy saves matmul outputs (backward multiplier 4->3)
    "remat_dots": {"param": {}, "act": {}},
    "flat_dp_dots": {},  # filled below: flat_dp sharding + dots remat
}
VARIANTS["flat_dp_dots"] = VARIANTS["flat_dp"]


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             rule_overrides: dict | None = None, dtype=jnp.bfloat16,
             microbatches: int | None = None, variant: str = "baseline") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "pod2x16x16" if multi_pod else "16x16"}
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    rules = SH.default_rules(multi_pod=multi_pod, fsdp=True,
                             seq_shard=(shape.kind in ("train", "prefill")))
    if variant != "baseline":
        rec["variant"] = variant
        vo = VARIANTS[variant]
        rule_overrides = {
            "param": {**vo.get("param", {}), **(rule_overrides or {}).get("param", {})},
            "act": {**vo.get("act", {}), **(rule_overrides or {}).get("act", {})},
        }
    if rule_overrides:
        rules = SH.ShardingRules(param={**rules.param, **rule_overrides.get("param", {})},
                                 act={**rules.act, **rule_overrides.get("act", {})})

    tmpl = M.template(cfg)
    aparams = abstract_from_template(tmpl, dtype)
    t0 = time.time()
    try:
        if shape.kind == "train":
            opt = AdamW(master_weights=True)
            mbs = microbatches if microbatches is not None else MICROBATCHES.get(arch, 1)
            rec["microbatches"] = mbs
            remat = "dots" if variant in ("remat_dots", "flat_dp_dots") else True
            jitted, _psh, _bsh = STEP.build_train_step(cfg, mesh, rules, opt,
                                                       microbatches=mbs,
                                                       remat=remat)
            aopt = abstract_opt_state(tmpl)
            abatch = M.input_specs(cfg, shape, dtype=dtype)
            lowered = jitted.lower(aparams, aopt, abatch)
        elif shape.kind == "prefill":
            jitted, _psh, _bsh = STEP.build_prefill_step(cfg, mesh, rules)
            abatch = M.input_specs(cfg, shape, dtype=dtype)
            lowered = jitted.lower(aparams, abatch)
        else:  # decode
            specs = M.input_specs(cfg, shape, dtype=dtype)
            jitted, _psh, _csh, _tsh = STEP.build_serve_step(
                cfg, mesh, rules, shape.global_batch, shape.seq_len
            )
            lowered = jitted.lower(aparams, specs["cache"], specs["token"], specs["pos"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    except Exception as e:  # a failing cell is a bug: record it loudly
        rec.update(status="FAILED", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        return rec

    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        mem = {
            "argument_bytes_per_device": int(ma.argument_size_in_bytes),
            "output_bytes_per_device": int(ma.output_size_in_bytes),
            "temp_bytes_per_device": int(ma.temp_size_in_bytes),
            "alias_bytes_per_device": int(ma.alias_size_in_bytes),
        }
        live = ma.argument_size_in_bytes + ma.temp_size_in_bytes + \
            ma.output_size_in_bytes - ma.alias_size_in_bytes
        mem["live_bytes_per_device"] = int(live)
        mem["fits_v5e_16GB"] = bool(live < RL.HBM_CAP)
    ca = compiled.cost_analysis() or {}
    cost = {"hlo_flops_per_device_body_once": float(ca.get("flops", 0.0)),
            "hlo_bytes_accessed_per_device_body_once": float(ca.get("bytes accessed", 0.0))}

    hlo = compiled.as_text()
    colls = RL.parse_collectives(hlo)
    dots = RL.parse_dot_flops(hlo)

    est = FL.estimate(cfg, shape, dict(mesh.shape),
                      remat_factor=3.0 if variant in ("remat_dots", "flat_dp_dots") else 4.0)
    # Variants change the collective schedule away from the analytic model's
    # assumptions: trust the HLO-parsed bytes there.
    if variant == "baseline":
        coll_bytes_dev = max(colls.bytes_weighted, est.collective_bytes_per_device)
    else:
        coll_bytes_dev = colls.bytes_weighted
    terms = RL.roofline_terms(est.flops_total, est.hbm_bytes_per_device,
                              coll_bytes_dev, chips)
    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=mem,
        cost_analysis=cost,
        collectives={"counts": colls.counts,
                     "bytes_static": colls.bytes_static,
                     "bytes_trip_weighted": colls.bytes_weighted},
        hlo_dot_flops=dots,
        analytic={
            "flops_total": est.flops_total,
            "flops_layer_fwd": est.flops_layer_fwd,
            "model_flops_6ND": est.model_flops,
            "useful_ratio": est.model_flops / est.flops_total if est.flops_total else 0.0,
            "hbm_bytes_per_device": est.hbm_bytes_per_device,
            "collective_bytes_per_device": est.collective_bytes_per_device,
        },
        roofline=terms,
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.variant != "baseline":
                    tag += f"__{args.variant}"
                path = outdir / f"{tag}.json"
                if path.exists():
                    print(f"[skip-cached] {tag}")
                    continue
                print(f"[run] {tag}", flush=True)
                rec = run_cell(arch, shape, mp, variant=args.variant,
                               microbatches=args.microbatches)
                path.write_text(json.dumps(rec, indent=1, default=str))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f" compile={rec['compile_s']}s"
                             f" live={rec['memory'].get('live_bytes_per_device', 0)/1e9:.2f}GB"
                             f" dom={rec['roofline']['dominant']}")
                    print(f"  memory_analysis: {rec['memory']}")
                    print(f"  cost_analysis:   {rec['cost_analysis']}")
                elif status == "FAILED":
                    extra = " " + rec["error"][:160]
                print(f"[{status}] {tag}{extra}", flush=True)


if __name__ == "__main__":
    main()
