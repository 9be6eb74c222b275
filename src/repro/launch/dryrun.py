import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512" + \
    (" " + os.environ.get("EXTRA_XLA_FLAGS", "")).rstrip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell on
512 placeholder host devices; record memory/cost/collective analysis.

The two lines above MUST precede any jax import (device count locks on
first init).  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b \
      --shape train_4k --mesh single            # one cell, prints JSON
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""
import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim, sharding
from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models import (SHAPES, abstract_train_state, input_specs,
                          make_decode_step, make_prefill_step,
                          make_train_step, shape_applicable)
from repro.models.steps import cache_logical_axes
from repro.roofline import hlo as hlo_mod
from repro.roofline.model import model_flops_for, roofline

BIG_ARCHS = {"deepseek-v3-671b", "command-r-plus-104b", "yi-34b",
             "chameleon-34b"}


def opt_config_for(arch: str) -> optim.OptConfig:
    if arch == "deepseek-v3-671b":
        return optim.OptConfig(kind="adafactor")
    if arch in BIG_ARCHS:
        return optim.OptConfig(kind="adamw", moment_dtype="bfloat16")
    return optim.OptConfig(kind="adamw")


def rules_for(arch: str, shape: str):
    over = {}
    if shape in ("prefill_32k",):
        over["seq"] = "model"          # SP for long prefill activations
    if arch in BIG_ARCHS:
        over["embed_fsdp"] = "data"
    if SHAPES[shape].kind == "decode":
        over["kv_seq"] = "model"       # sequence-sharded KV caches
    return sharding.with_rules(over)


def build_mesh(mesh_kind: str):
    if mesh_kind == "single":
        return make_production_mesh(multi_pod=False)
    if mesh_kind == "multi":
        return make_production_mesh(multi_pod=True)
    if mesh_kind == "test-single":
        return make_test_mesh(multi_pod=False)
    if mesh_kind == "test-multi":
        return make_test_mesh(multi_pod=True)
    raise ValueError(mesh_kind)


def lower_cell(arch: str, shape: str, mesh_kind: str,
               include_hlo_stats: bool = True):
    """Lower+compile one cell; returns a JSON-able result dict."""
    cfg = get_config(arch)
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped",
                "reason": "full-attention arch: 500k dense KV cache is the "
                          "quadratic regime this shape excludes"}
    mesh = build_mesh(mesh_kind)
    rules = rules_for(arch, shape)
    s = SHAPES[shape]
    t0 = time.time()

    with sharding.use_mesh(mesh, rules):
        batch, batch_logical = input_specs(cfg, shape)
        batch_sh = sharding.tree_shardings(batch_logical, mesh, rules,
                                           shape_tree=batch)
        if s.kind == "train":
            opt_cfg = opt_config_for(arch)
            params, pspecs, opt_state, ospecs = abstract_train_state(
                cfg, opt_cfg)
            p_sh = sharding.tree_shardings(pspecs, mesh, rules,
                                           shape_tree=params)
            o_sh = sharding.tree_shardings(ospecs, mesh, rules,
                                           shape_tree=opt_state)
            step = make_train_step(cfg, opt_cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, batch_sh),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            args = (params, opt_state, batch)
        elif s.kind == "prefill":
            params, pspecs, _, _ = abstract_train_state(
                cfg, opt_config_for(arch))
            p_sh = sharding.tree_shardings(pspecs, mesh, rules,
                                           shape_tree=params)
            step = make_prefill_step(cfg, total_len=s.seq_len)
            jitted = jax.jit(step, in_shardings=(p_sh, batch_sh))
            args = (params, batch)
        else:  # decode
            params, pspecs, _, _ = abstract_train_state(
                cfg, opt_config_for(arch))
            p_sh = sharding.tree_shardings(pspecs, mesh, rules,
                                           shape_tree=params)
            step = make_decode_step(cfg)
            jitted = jax.jit(
                step, in_shardings=(p_sh, batch_sh),
                out_shardings=None, donate_argnums=())
            args = (params, batch)

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    n_dev = int(np.prod(mesh.devices.shape))

    result = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "status": "ok",
        "chips": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "cost_analysis": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
    }
    if include_hlo_stats:
        text = compiled.as_text()
        stats = hlo_mod.analyze(text)
        result["hlo"] = {
            "collective_bytes": stats.collective_bytes,
            "collective_bytes_by_kind": stats.collective_bytes_by_kind,
            "collective_count": stats.collective_count,
            "dot_flops": stats.dot_flops,
            "traffic_bytes": stats.traffic_bytes,
            "traffic_bytes_fused": stats.traffic_bytes_fused,
            "while_trip_counts": stats.while_trip_counts,
            "hlo_chars": len(text),
        }
        mf = model_flops_for(cfg, s.kind, s.seq_len, s.global_batch)
        # loop-corrected per-device flops: prefer our dot census (scan-aware)
        pd_flops = max(stats.dot_flops, cost.get("flops") or 0.0)
        rl = roofline(pd_flops, stats.traffic_bytes_fused,
                      stats.collective_bytes, n_dev, mf)
        rl_raw = roofline(pd_flops, stats.traffic_bytes,
                          stats.collective_bytes, n_dev, mf)
        result["roofline"] = {
            "compute_s": rl.compute_s, "memory_s": rl.memory_s,
            "memory_s_raw": rl_raw.memory_s,
            "collective_s": rl.collective_s, "bottleneck": rl.bottleneck,
            "step_time_s": rl.step_time_s, "mfu": rl.mfu,
            "mfu_raw": rl_raw.mfu,
            "model_flops": mf, "flops_global": rl.flops_global,
            "useful_ratio": rl.useful_ratio,
        }
        del text
    del compiled, lowered
    gc.collect()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "test-single", "test-multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="directory for JSON results")
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape, "single"))
                cells.append((arch, shape, "multi"))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, args.mesh)]

    for arch, shape, mesh_kind in cells:
        tag = f"{arch}__{shape}__{mesh_kind}"
        try:
            res = lower_cell(arch, shape, mesh_kind,
                             include_hlo_stats=not args.no_hlo)
        except Exception as e:  # noqa: BLE001 — report, don't die mid-sweep
            res = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                   "status": "error", "error": repr(e),
                   "trace": traceback.format_exc()[-4000:]}
        js = json.dumps(res, indent=1, default=float)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                f.write(js)
            print(tag, res["status"], flush=True)
        else:
            print(js, flush=True)


if __name__ == "__main__":
    main()
