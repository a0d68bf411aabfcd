import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
512 placeholder CPU devices standing in for the production TPU mesh.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Writes one JSON per cell under experiments/dryrun/ containing
memory_analysis, cost_analysis, parsed collective bytes, and roofline terms.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis.flops import step_flops, step_hbm_bytes
from ..analysis.hlo_parse import HloCosts
from ..analysis.roofline import (HW, collective_bytes_from_hlo, model_flops,
                                 roofline_terms, summarize_memory)
from ..configs import (SHAPE_BY_NAME, SHAPES, ARCH_IDS, cell_is_runnable,
                       get_config)
from ..dist.sharding import Rules, use_rules
from ..launch.mesh import make_production_mesh
from ..launch.specs import (batch_specs, cache_specs, decode_inputs,
                            safe_sharding, state_specs)
from ..serve.serve_step import make_decode_step, make_prefill_step
from ..train.optimizer import OptConfig
from ..train.train_step import make_train_step

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def rules_kind(shape) -> str:
    if shape.kind == "train":
        return "train"
    if shape.name.startswith("long"):
        return "long"
    return shape.kind


def lower_cell(cfg, shape, mesh, *, extra_tag: str = "", step_override=None,
               policy: str = "tp"):
    """Lower + compile one cell. Returns the result record."""
    kind = rules_kind(shape)
    rules = Rules(mesh, kind, policy, global_batch=shape.global_batch)
    t0 = time.time()
    with jax.set_mesh(mesh), use_rules(rules):
        if shape.kind == "train":
            params, pshard, opt, oshard = state_specs(cfg, rules)
            batch, bshard = batch_specs(cfg, shape, rules, "train")
            step = step_override or make_train_step(
                cfg, OptConfig(), accum_steps=getattr(cfg, "accum_steps", 1))
            jitted = jax.jit(
                step,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params, opt, batch)
        elif shape.kind == "prefill":
            params, pshard, _, _ = state_specs(cfg, rules, dtype=jnp.bfloat16)
            batch, bshard = batch_specs(cfg, shape, rules, "prefill")
            _, cshard = cache_specs(cfg, shape, rules)
            logits_shard = safe_sharding(mesh, rules.spec("batch", "vocab"),
                                         (shape.global_batch, cfg.vocab_size))
            step = step_override or make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(pshard, bshard),
                             out_shardings=(logits_shard, cshard))
            lowered = jitted.lower(params, batch)
        else:  # decode
            params, pshard, _, _ = state_specs(cfg, rules, dtype=jnp.bfloat16)
            cache, cshard = cache_specs(cfg, shape, rules)
            (token, tshard), (pos, posshard) = decode_inputs(cfg, shape, rules)
            logits_shard = safe_sharding(mesh, rules.spec("batch", None, "vocab"),
                                         (shape.global_batch, 1, cfg.vocab_size))
            step = step_override or make_decode_step(cfg)
            jitted = jax.jit(step,
                             in_shardings=(pshard, cshard, tshard, posshard),
                             out_shardings=(logits_shard, cshard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params, cache, token, pos)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = summarize_memory(compiled.memory_analysis())
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    n_chips = mesh.devices.size
    # loop-aware collective accounting (per-chip byte totals; see hlo_parse)
    coll = HloCosts(hlo).collective_bytes()
    coll["naive"] = collective_bytes_from_hlo(hlo)   # loop bodies counted once
    # analytic flops/bytes (cost_analysis undercounts scanned loops)
    fl = step_flops(cfg, shape, shape.kind)
    flops_per_chip = fl["total"] / n_chips
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    bytes_per_chip = step_hbm_bytes(cfg, shape, shape.kind, n_chips, tp)
    terms = roofline_terms(flops_per_chip, bytes_per_chip,
                           coll.get("tpu_bf16_adjusted_bytes",
                                    coll["weighted_bytes"]))
    terms["collective_raw_s"] = coll["weighted_bytes"] / 50e9
    mf = model_flops(cfg, shape, shape.kind)
    rec = {
        "arch": cfg.name, "shape": shape.name, "kind": shape.kind,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "mesh_axes": list(mesh.axis_names), "n_chips": int(n_chips),
        "tag": extra_tag,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem,
        "flops_per_chip": flops_per_chip,
        "flops_breakdown": fl,
        "bytes_per_chip": bytes_per_chip,
        "cost_analysis_raw": {"flops": float(cost.get("flops", 0.0)),
                              "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "collectives": coll,
        "roofline": terms,
        "model_flops_total": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flop_ratio": (mf / n_chips) / flops_per_chip if flops_per_chip else None,
        "hbm_per_chip_gb": round(mem.get("peak_est_bytes", 0) / 2**30, 3),
    }
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool, tag: str = "",
             policy: str = "tp"):
    cfg = get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why,
                "mesh": "multi" if multi_pod else "single"}
    if policy == "auto":
        # per-arch policies are tuned for training; inference shapes keep the
        # standard TP mesh (tp2d's reshaped mesh hurt llama4 prefill 30x)
        policy = cfg.preferred_policy if shape.kind == "train" else (
            "tp" if cfg.preferred_policy == "tp2d" else cfg.preferred_policy)
    if policy == "tp2d":
        from ..launch.mesh import make_tp2d_mesh
        mesh = make_tp2d_mesh(multi_pod=multi_pod)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    return lower_cell(cfg, shape, mesh, extra_tag=tag, policy=policy)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--policy", default="tp",
                    choices=("tp", "fsdp", "tp2d", "auto"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = [s.name for s in SHAPES] if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mtag = "multi" if mp else "single"
                name = f"{arch}_{shape}_{mtag}" + (f"_{args.tag}" if args.tag else "")
                out = Path(args.out) if args.out else OUT_DIR / f"{name}.json"
                try:
                    rec = run_cell(arch, shape, mp, args.tag, args.policy)
                    out.write_text(json.dumps(rec, indent=1))
                    if "skipped" in rec:
                        print(f"[skip] {name}: {rec['skipped']}")
                    else:
                        r = rec["roofline"]
                        print(f"[ok]   {name}: bound={r['bound']} "
                              f"c={r['compute_s']:.4f}s m={r['memory_s']:.4f}s "
                              f"x={r['collective_s']:.4f}s "
                              f"hbm={rec['hbm_per_chip_gb']}GB "
                              f"compile={rec['compile_s']}s", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures.append(name)
                    out.write_text(json.dumps(
                        {"arch": arch, "shape": shape, "mesh": mtag,
                         "error": f"{type(e).__name__}: {e}"}, indent=1))
                    print(f"[FAIL] {name}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nall cells passed")


if __name__ == "__main__":
    main()
