import os
# A CPU-only tool on placeholder devices: pinned to the CPU (children
# inherit the environment) so that it never takes an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any jax import (device count locks at
# first init).  Tests may shrink the placeholder pool via REPRO_DRYRUN_DEVICES.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=" + os.environ["REPRO_DRYRUN_DEVICES"]
    )

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_arch, param_counts, shape_applicable
from repro.launch.inputs import input_specs
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_mesh,
                               make_production_mesh)
from repro.models.model import model_flops_per_token, params_shape
from repro.optim import adamw
from repro.roofline import flops as hlo_flops
from repro.roofline import hlo as hlo_mod
from repro.sharding import specs as sh
from repro.sharding.ctx import sharding_rules
from repro.train.step import make_prefill_step, make_serve_step, make_train_step

FSDP_PARAM_THRESHOLD = 20e9  # params: above this, weights/opt shard over data too
BF16_OPT_THRESHOLD = 150e9  # params: above this, bf16 moments + no fp32 master


def _attach(struct_tree, spec_tree):
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sp),
        struct_tree, spec_tree,
    )


def opt_config(total_params: float) -> adamw.AdamWConfig:
    if total_params >= BF16_OPT_THRESHOLD:
        return adamw.AdamWConfig(moment_dtype="bfloat16", master_weights=False)
    return adamw.AdamWConfig(moment_dtype="float32", master_weights=False)


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, reduced: bool = False,
             overrides=None) -> dict:
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    overrides = dict(overrides) if overrides else {}
    tp2d_flag = bool(overrides.pop("tp2d", False))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
    }
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["why"] = why
        return rec

    if reduced:  # CI smoke: tiny mesh on the shrunken device pool
        shape_ax = (2, 2, 2) if multi_pod else (2, 2)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        mesh = make_mesh(shape_ax, axes)
        rec["mesh"] = "x".join(map(str, shape_ax))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    total, active = param_counts(cfg)
    fsdp = total >= FSDP_PARAM_THRESHOLD
    tp2d = tp2d_flag

    pstruct = params_shape(cfg)
    pspecs = sh.param_specs(pstruct, mesh, fsdp=fsdp, tp2d=tp2d)
    pstruct = _attach(pstruct, pspecs)
    rules = sharding_rules(sh.activation_rules(cfg, mesh, batch=shape.global_batch))

    t0 = time.time()
    if shape.kind == "train":
        ocfg = opt_config(total)
        ostruct = jax.eval_shape(lambda p: adamw.init(ocfg, p), pstruct)
        ospecs = sh.opt_state_specs(pspecs, ostruct, mesh)
        ostruct = _attach(ostruct, ospecs)
        bstruct = input_specs(cfg, shape)["batch"]
        bstruct = _attach(bstruct, sh.batch_specs(bstruct, mesh, batch=shape.global_batch))
        step = make_train_step(cfg, ocfg)
        with rules:
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(pstruct, ostruct, bstruct)
    elif shape.kind == "prefill":
        bstruct = input_specs(cfg, shape)["batch"]
        bstruct = _attach(bstruct, sh.batch_specs(bstruct, mesh, batch=shape.global_batch))
        step = make_prefill_step(cfg)
        with rules:
            lowered = jax.jit(step).lower(pstruct, bstruct)
    else:  # decode
        ins = input_specs(cfg, shape)
        cstruct = ins["cache"]
        cspecs = sh.cache_specs(cstruct, mesh, batch=shape.global_batch, tp2d=tp2d)
        cstruct = _attach(cstruct, cspecs)
        tstruct = ins["token"]
        tstruct = _attach(tstruct, sh.batch_specs(tstruct, mesh, batch=shape.global_batch))
        step = make_serve_step(cfg)
        with rules:
            lowered = jax.jit(step, donate_argnums=(1,)).lower(pstruct, cstruct, tstruct)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, list):  # older jax returns [dict], newer returns dict
        ca = ca[0] if ca else {}
    text = compiled.as_text()
    mine = hlo_flops.analyze(text)
    colls = hlo_mod.collective_summary(text)
    link_bytes = sum(e["link_bytes"] for e in colls.values())

    # per-device, per-step roofline terms (seconds)
    flops_pd = mine["flops"]
    bytes_pd = mine["bytes"]
    compute_s = flops_pd / PEAK_FLOPS_BF16
    memory_s = bytes_pd / HBM_BW
    collective_s = link_bytes / ICI_BW

    # MODEL_FLOPS: 6*N*D for training (fwd 2 + bwd 4), 2*N*D for inference
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train" else
                                   (shape.seq_len if shape.kind == "prefill" else 1))
    per_token = model_flops_per_token(cfg)  # = 6*N_active
    if shape.kind != "train":
        per_token /= 3.0  # 2*N_active
    model_flops = per_token * tokens
    model_flops_pd = model_flops / n_chips

    dom = max(("compute", compute_s), ("memory", memory_s), ("collective", collective_s),
              key=lambda kv: kv[1])[0]
    rec.update({
        "status": "ok",
        "n_chips": n_chips,
        "fsdp": fsdp,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": getattr(ma, "peak_memory_in_bytes", 0),
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "xla_cost": {"flops": ca.get("flops", 0.0),
                     "bytes_accessed": ca.get("bytes accessed", 0.0)},
        "loop_aware": {"flops_per_device": flops_pd, "bytes_per_device": bytes_pd},
        "collectives": colls,
        "collective_link_bytes_per_device": link_bytes,
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dom,
        },
        "model_flops_per_device": model_flops_pd,
        "useful_flops_ratio": (model_flops_pd / flops_pd) if flops_pd else 0.0,
        "params_total": total,
        "params_active": active,
    })
    return rec


def cells(archs=None, shapes=None):
    for a in (archs or ARCHS):
        for s in (shapes or SHAPES):
            yield a, s


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run: lower + compile + roofline terms")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--reduced", action="store_true", help="reduced configs (CI smoke)")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each cell in a fresh process (bounds compiler RSS)")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. attn_chunk=1024)")
    args = ap.parse_args(argv)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    failures = 0
    for a, s in cells(archs, shapes):
        for mp in meshes:
            tag = f"{a}_{s}_{'multi' if mp else 'single'}"
            path = out_dir / f"{tag}.json"
            if args.subprocess:
                cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
                       "--shape", s, "--mesh", "multi" if mp else "single",
                       "--out", str(out_dir)]
                if args.reduced:
                    cmd.append("--reduced")
                for kv in args.set:
                    cmd += ["--set", kv]
                r = subprocess.run(cmd, capture_output=True, text=True)
                tail = "\n".join(r.stdout.splitlines()[-3:])
                print(f"[{tag}] rc={r.returncode} {tail}")
                if r.returncode != 0:
                    failures += 1
                    print(r.stderr[-2000:])
                continue
            try:
                rec = run_cell(a, s, mp, reduced=args.reduced, overrides=overrides)
            except Exception:
                rec = {"arch": a, "shape": s,
                       "mesh": "2x16x16" if mp else "16x16",
                       "status": "error", "traceback": traceback.format_exc()}
                failures += 1
            path.write_text(json.dumps(rec, indent=1, default=float))
            if rec["status"] == "ok":
                r = rec["roofline"]
                print(f"[{tag}] ok compile={rec['compile_s']}s "
                      f"compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
                      f"collective={r['collective_s']*1e3:.2f}ms dominant={r['dominant']} "
                      f"useful={rec['useful_flops_ratio']:.2f}")
            elif rec["status"] == "skipped":
                print(f"[{tag}] SKIP: {rec['why']}")
            else:
                print(f"[{tag}] ERROR (see {path})")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
