"""Multi-pod dry-run: lower + compile every (arch × input shape) on the
production meshes and extract memory/cost/collective analyses.

Usage:
    python -m repro.launch.dryrun --arch olmo-1b --shape train_4k --mesh pod
    python -m repro.launch.dryrun --all --mesh pod --out dryrun_out
    python -m repro.launch.dryrun --all --mesh 2pod   # 512-chip multi-pod pass

This container has ONE real CPU device; the 512 placeholder devices below
exist only so ``jax.make_mesh`` can build the production meshes.  This is
the ONLY module that sets the flag, and it must run before any jax import.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, INPUT_SHAPES, get_config,
                           shape_applicable)
from repro.launch.input_specs import input_specs
from repro.launch.mesh import make_production_mesh, mesh_devices
from repro.launch.sharding_rules import (batch_shardings, cache_shardings,
                                         param_shardings, replicated)
from repro.models import build_model
from repro.roofline import analysis as roofline
from repro.models.common import set_activation_sharding, set_scan_unroll
from repro.train.optimizer import AdamW, AdamWState
from repro.train.train_step import make_train_step, pick_accum_steps


def _reduced_layers(cfg, n: int):
    """Same architecture with n scan steps (for cost extrapolation)."""
    pat = len(cfg.block_pattern) or 1
    return dataclasses.replace(
        cfg, num_layers=n * pat,
        encoder_layers=n if cfg.encoder_layers else 0)


def build_step(cfg, shape, mesh, fsdp=True, roofline_variant=False,
               opts=frozenset(), accum_override=None):
    """Returns (jitted_fn, abstract_args) for the combo.

    ``roofline_variant=True`` lowers the cost-extrapolation variant:
    accumulation forced to 1 (full batch in one microbatch) and CE in a
    single chunk, so every non-layer scan has trip count 1 and
    cost_analysis counts it exactly (EXPERIMENTS.md §Roofline method).

    ``opts`` — beyond-paper optimizations measured in §Perf:
      * "bf16_inference": prefill/decode weights held in bf16 (halves
        weight-streaming and gather bytes; matmuls are bf16 anyway);
      * "tp_decode_weights": drop FSDP on decode when the model fits
        TP-only residency (kills the per-layer weight all-gathers);
      * "pad_experts": round the expert count up to the model-axis width
        (60 -> 64 on the 16-wide axis) so the expert dimension shards —
        the standard deployment remedy for indivisible expert counts
        (pad experts receive zero routing mass in a real run).
    """
    if "pad_experts" in opts and cfg.num_experts:
        axis = mesh.shape["model"]
        if cfg.num_experts % axis:
            padded = -(-cfg.num_experts // axis) * axis
            cfg = dataclasses.replace(cfg, num_experts=padded)
    from repro.launch.mesh import data_axes
    import numpy as _np
    set_activation_sharding(data_axes(mesh))
    model = build_model(cfg)
    bundle = input_specs(cfg, shape, model)
    params = model.abstract_params()
    if "bf16_inference" in opts and bundle.kind != "train":
        params = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)
    if "bf16_train_params" in opts and bundle.kind == "train":
        # bf16 weights + fp32 Adam moments: halves FSDP gather and grad
        # all-reduce bytes (§Perf HC3); documented quality caveat.
        params = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)
    if "tp_decode_weights" in opts and bundle.kind == "decode":
        pbytes = sum(int(_np.prod(x.shape)) *
                     (2 if x.dtype == jnp.bfloat16 else 4)
                     for x in jax.tree_util.tree_leaves(params))
        if pbytes / mesh.shape["model"] < 8 * 2 ** 30:
            fsdp = False
    pshard = param_shardings(cfg, mesh, params, fsdp=fsdp)

    if bundle.kind == "train":
        opt = AdamW()
        opt_state = jax.eval_shape(opt.init, params)
        oshard = AdamWState(replicated(mesh, opt_state.step), pshard, pshard)
        batch = bundle.args[0]
        bshard = batch_shardings(cfg, mesh, batch)
        dp = int(_np.prod([mesh.shape[a] for a in data_axes(mesh)]))
        accum = 1 if roofline_variant else (
            accum_override or pick_accum_steps(cfg, shape, dp))
        ce_chunk = shape.seq_len if roofline_variant else 512
        step = make_train_step(model, opt, accum_steps=accum,
                               ce_chunk=ce_chunk)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mshard = {k: NamedSharding(mesh, P())
                  for k in ("loss", "grad_norm", "lr")}
        fn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, mshard),
                     donate_argnums=(0, 1))
        return fn, (params, opt_state, batch)

    if bundle.kind == "prefill":
        batch = bundle.args[0]
        bshard = batch_shardings(cfg, mesh, batch)

        def prefill(params, batch):
            logits, _ = model.forward(params, batch["tokens"],
                                      batch.get("patch_embeds"),
                                      batch.get("frames"))
            return logits

        fn = jax.jit(prefill, in_shardings=(pshard, bshard))
        return fn, (params, batch)

    # decode
    caches, tokens, pos = bundle.args[:3]
    enc = bundle.args[3] if len(bundle.args) > 3 else None
    cshard = cache_shardings(cfg, mesh, caches)
    tshard = batch_shardings(cfg, mesh, {"t": tokens, "p": pos})
    in_sh = [pshard, cshard, tshard["t"], tshard["p"]]
    args = [params, caches, tokens, pos]
    if enc is not None:
        in_sh.append(batch_shardings(cfg, mesh, {"e": enc})["e"])
        args.append(enc)

    def decode(params, caches, tokens, pos, *rest):
        return build_model(cfg).decode_step(params, caches, tokens, pos,
                                            *rest)

    fn = jax.jit(decode, in_shardings=tuple(in_sh), donate_argnums=(1,))
    return fn, tuple(args)


def run_combo(arch: str, shape_name: str, mesh, mesh_name: str,
              fsdp: bool = True, skip_extrapolation: bool = False,
              opts=frozenset(), accum_override=None) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "devices": mesh_devices(mesh), "kind": shape.kind,
    }
    skip = shape_applicable(cfg, shape)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    rec["opts"] = sorted(opts)
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, args = build_step(cfg, shape, mesh, fsdp=fsdp, opts=opts,
                              accum_override=accum_override)
        lowered = fn.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes_per_device": int(ma.argument_size_in_bytes),
            "temp_bytes_per_device": int(ma.temp_size_in_bytes),
            "output_bytes_per_device": int(ma.output_size_in_bytes),
            "total_gib_per_device": round(
                (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 - ma.alias_size_in_bytes) / 2**30, 3),
        }
        costs_full = roofline.raw_costs(compiled)

        model = build_model(cfg)
        scan_layers = model._num_scan_layers()
        decode_unrolled = (shape.kind == "decode"
                           and not model.uniform_cache())
        if decode_unrolled or skip_extrapolation:
            # heterogeneous-cache decode unrolls layers: fully counted
            costs = costs_full
            rec["extrapolated"] = False
        else:
            c1 = c2 = None
            set_scan_unroll(True)   # unrolled variants: exact per-layer cost
            try:
                for n in (1, 2):
                    cfg_n = _reduced_layers(cfg, n)
                    fn_n, args_n = build_step(cfg_n, shape, mesh, fsdp=fsdp,
                                              roofline_variant=True,
                                              opts=opts)
                    comp_n = fn_n.lower(*args_n).compile()
                    c = roofline.raw_costs(comp_n)
                    c1, c2 = (c, c2) if n == 1 else (c1, c)
            finally:
                set_scan_unroll(False)
            costs = roofline.extrapolate(c1, c2, scan_layers)
            corr = roofline.inner_scan_corrections(cfg, shape,
                                                   mesh_devices(mesh))
            if shape.kind == "decode":
                corr = {"flops": 0.0, "bytes": 0.0}
            costs.flops += corr["flops"]
            costs.bytes_accessed += corr["bytes"]
            rec["extrapolated"] = True
            rec["analytic_correction"] = corr
            rec["per_layer_flops"] = c2.flops - c1.flops

    rec["costs"] = {
        "flops_per_device": costs.flops,
        "bytes_per_device": costs.bytes_accessed,
        "collective_bytes_per_device": costs.coll_bytes,
        "collective_detail": costs.coll_detail,
    }
    terms = roofline.roofline_terms(costs)
    rec["roofline"] = terms
    mf = roofline.model_flops(cfg, shape)
    hlo_global = costs.flops * mesh_devices(mesh)
    rec["model_flops_global"] = mf
    rec["hlo_flops_global"] = hlo_global
    rec["useful_compute_ratio"] = round(mf / hlo_global, 4) if hlo_global else 0
    rec["status"] = "ok"
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=["pod", "2pod"], default="pod")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) combination")
    ap.add_argument("--out", default="dryrun_out")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip combos whose result JSON already exists")
    ap.add_argument("--opt", default="",
                    help="comma-separated optimizations "
                         "(bf16_inference,tp_decode_weights)")
    ap.add_argument("--accum", type=int, default=None,
                    help="override gradient-accumulation steps")
    ap.add_argument("--tag", default="",
                    help="suffix for result filenames (A/B experiments)")
    ap.add_argument("--skip-roofline", action="store_true",
                    help="compile-only pass (no L-extrapolation variants); "
                         "used for the multi-pod mesh, whose deliverable is "
                         "the successful lower+compile")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=(args.mesh == "2pod"))
    os.makedirs(args.out, exist_ok=True)

    combos = ([(args.arch, args.shape)] if not args.all else
              [(a, s) for a in ARCH_IDS for s in INPUT_SHAPES])
    if not args.all and (args.arch is None or args.shape is None):
        ap.error("--arch and --shape required unless --all")

    opts = frozenset(x for x in args.opt.split(",") if x)
    failures = 0
    for arch, shape in combos:
        tag = f"{arch}__{shape}__{args.mesh}" + (
            f"__{args.tag}" if args.tag else "")
        path0 = os.path.join(args.out, tag + ".json")
        if args.resume and os.path.exists(path0):
            with open(path0) as f:
                prev = json.load(f)
            if prev.get("status") in ("ok", "skipped"):
                print(f"[cached ] {tag}", flush=True)
                continue
        try:
            rec = run_combo(arch, shape, mesh, args.mesh,
                            fsdp=not args.no_fsdp, opts=opts,
                            accum_override=args.accum,
                            skip_extrapolation=args.skip_roofline)
        except Exception as e:  # noqa: BLE001 — record and continue
            rec = {"arch": arch, "shape": shape, "mesh": args.mesh,
                   "status": "failed", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            failures += 1
        path = os.path.join(args.out, tag + ".json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" dom={r['dominant']} "
                     f"c={r['compute_s']:.2e} m={r['memory_s']:.2e} "
                     f"x={r['collective_s']:.2e} "
                     f"mem/dev={rec['memory']['total_gib_per_device']}GiB "
                     f"compile={rec['compile_s']}s")
        elif status == "skipped":
            extra = " " + rec["reason"][:60]
        else:
            extra = " " + rec["error"][:120]
        print(f"[{status:7s}] {tag}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} combos failed")


if __name__ == "__main__":
    main()
