import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init). This module is the ONLY place the 512 placeholder
# devices exist; tests and benchmarks see the real single device.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
the production mesh, proving the distribution config is coherent, and
extract the roofline terms (FLOPs / bytes / collective bytes) from the
compiled artifact.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
        --shape train_4k [--multi-pod] [--out results/]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import dataclasses
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.registry import SHAPES, cells, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model, abstract_params
from repro.optim import adamw as opt_lib
from repro.serve.engine import make_prefill, make_serve_step
from repro.sharding import rules
from repro.sharding.spec import from_mesh
from repro.train.step import TrainConfig, make_train_step


def _expert_2d(cfg: ModelConfig, mesh) -> bool:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    group = sizes.get("data", 1) * sizes.get("model", 1)
    return cfg.n_experts > 0 and cfg.n_experts % group == 0 and cfg.n_experts >= group


def pick_accum(cfg: ModelConfig, global_batch: int, batch_div: int) -> int:
    """Largest accum <= cfg.grad_accum with microbatch divisible by the
    data-parallel extent (multi-pod doubles the batch axes product)."""
    a = min(cfg.grad_accum, max(1, global_batch // max(batch_div, 1)))
    while a > 1 and (global_batch % a or (global_batch // a) % batch_div):
        a -= 1
    return max(a, 1)


def input_specs(cfg: ModelConfig, shape_name: str, *, accum: int | None = None,
                batch_div: int = 1):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    seq, batch, kind = SHAPES[shape_name]
    dt = jnp.dtype(cfg.dtype)
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if kind == "train":
        accum = accum or pick_accum(cfg, batch, batch_div)
        b = batch // accum
        spec = {
            "tokens": sds((accum, b, seq), i32),
            "labels": sds((accum, b, seq), i32),
        }
        if cfg.encoder_segments:
            spec["frames"] = sds((accum, b, seq, cfg.d_model), dt)
        if cfg.n_vision_tokens:
            spec["vision"] = sds((accum, b, cfg.n_vision_tokens, cfg.d_model), dt)
        return spec
    if kind == "prefill":
        spec = {"tokens": sds((batch, seq), i32)}
        if cfg.encoder_segments:
            spec["frames"] = sds((batch, seq, cfg.d_model), dt)
        if cfg.n_vision_tokens:
            spec["vision"] = sds((batch, cfg.n_vision_tokens, cfg.d_model), dt)
        return spec
    # decode: one new token against a seq-long cache
    return {"tokens": sds((batch, 1), i32)}


def _shardings(mesh, tree_specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


OPT1_FLAGS = ("decode_moe_ep", "flash_attention", "seq_shard_cache")
# seq_parallel is NOT in the default opt set: §Perf iteration C7 showed it
# regresses dense/SSM trains 10-30x on Tcoll (GSPMD replicates any weight
# whose projection output is not explicitly pinned); it stays available
# via --opt-flags for archs with fully-pinned projections.
OPT2_FLAGS = OPT1_FLAGS + ("hierarchical_a2a",)
# per-arch extras: v3's MLA projections are explicitly pinned (C5), so SP
# is a win there (17.8s vs 20.1s Tcoll on train_4k) and only there.
OPT_ARCH_EXTRA = {"deepseek-v3-671b": ("seq_parallel",)}


def lower_cell(arch: str, shape_name: str, mesh, *, verbose: bool = True,
               cfg: ModelConfig | None = None, opt: bool = False,
               opt_flags: tuple = OPT2_FLAGS):
    """Lower + compile one (arch x shape) cell on ``mesh``.

    ``opt=True`` enables the beyond-baseline variants recorded in
    EXPERIMENTS.md §Perf: sequence-sharded decode caches, EP(data) x
    TP(model) decode MoE, flash (two-level online-softmax) attention.

    Returns dict with cost analysis, memory analysis, and collective-bytes
    parsed from the optimized HLO."""
    cfg = cfg or get_config(arch)
    seq_shard_cache = False
    if opt:
        opt_flags = tuple(opt_flags) + OPT_ARCH_EXTRA.get(arch, ())
        cfg_flags = {f: True for f in opt_flags if f != "seq_shard_cache"}
        cfg = dataclasses.replace(cfg, **cfg_flags)
        seq_shard_cache = "seq_shard_cache" in opt_flags
    seq, batch, kind = SHAPES[shape_name]
    axes = from_mesh(mesh, expert_2d=_expert_2d(cfg, mesh))
    model = Model(cfg, axes)

    aparams = abstract_params(cfg, axes=axes)
    # decode-mode expert sharding applies ONLY to the decode step; prefill
    # runs the EP dispatch and must see train-style expert sharding.
    pspecs = rules.param_specs(aparams, cfg, axes, mode="decode" if kind == "decode" else "train")
    t0 = time.time()

    with jax.set_mesh(mesh):
        if kind == "train":
            tcfg = TrainConfig(
                opt=opt_lib.OptConfig(
                    name=cfg.optimizer, state_dtype=cfg.opt_state_dtype
                ),
                accum_dtype="bfloat16" if cfg.opt_state_dtype == "bfloat16" else "float32",
            )
            astate = jax.eval_shape(
                lambda p: opt_lib.init_opt_state(p, tcfg.opt), aparams
            )
            sspecs = rules.opt_state_specs(astate, pspecs, cfg, axes, zero=True)
            batch_div = 1
            for a in axes.batch:
                batch_div *= axes.mesh_shape[a]
            abatch = input_specs(cfg, shape_name, batch_div=batch_div)
            bspecs = rules.batch_specs(abatch, axes, train=True)
            step_fn = make_train_step(model, tcfg)
            jitted = jax.jit(
                step_fn,
                in_shardings=(
                    _shardings(mesh, pspecs),
                    _shardings(mesh, sspecs),
                    None,
                    _shardings(mesh, bspecs),
                ),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(
                aparams, astate, jax.ShapeDtypeStruct((), jnp.int32), abatch
            )
        elif kind == "prefill":
            abatch = input_specs(cfg, shape_name)
            bspecs = rules.batch_specs(abatch, axes, train=False)
            prefill = make_prefill(model)
            jitted = jax.jit(
                prefill,
                in_shardings=(_shardings(mesh, pspecs), _shardings(mesh, bspecs)),
            )
            lowered = jitted.lower(aparams, abatch)
        else:  # decode
            mem_len = 0
            if cfg.encoder_segments:
                mem_len = seq
            elif cfg.n_vision_tokens:
                mem_len = cfg.n_vision_tokens
            acaches = jax.eval_shape(
                lambda: model.init_caches(batch, seq, memory_len=mem_len)
            )
            cspecs = rules.cache_specs(acaches, cfg, axes, seq_shard=seq_shard_cache)
            abatch = input_specs(cfg, shape_name)
            bspecs = rules.batch_specs(abatch, axes, train=False)
            serve_step = make_serve_step(model)
            jitted = jax.jit(
                serve_step,
                in_shardings=(
                    _shardings(mesh, pspecs),
                    _shardings(mesh, cspecs),
                    _shardings(mesh, bspecs["tokens"]),
                    None,
                ),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(
                aparams, acaches, abatch["tokens"], jax.ShapeDtypeStruct((), jnp.int32)
            )

        compiled = lowered.compile()

    elapsed = time.time() - t0
    result = analyze(compiled, mesh, cfg, shape_name)
    result.update(arch=arch, shape=shape_name, kind=kind,
                  mesh="x".join(str(s) for s in mesh.devices.shape),
                  compile_s=round(elapsed, 1))
    if verbose:
        mem = result.get("bytes_per_device_gb")
        print(f"[dryrun] {arch} x {shape_name} on {result['mesh']}: "
              f"compiled in {elapsed:.0f}s, {mem} GB/device, "
              f"flops/dev={result['flops_per_device']:.3e}")
    return result


def analyze(compiled, mesh, cfg: ModelConfig, shape_name: str) -> dict:
    from repro.launch import hlo_stats

    n_dev = mesh.devices.size
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # jax 0.4.x: one dict per device
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        per_dev_bytes = getattr(mem, "temp_size_in_bytes", 0) + getattr(
            mem, "argument_size_in_bytes", 0
        ) + getattr(mem, "output_size_in_bytes", 0) - getattr(
            mem, "alias_size_in_bytes", 0
        )
        mem_detail = {
            "temp_gb": round(getattr(mem, "temp_size_in_bytes", 0) / 2**30, 3),
            "args_gb": round(getattr(mem, "argument_size_in_bytes", 0) / 2**30, 3),
            "output_gb": round(getattr(mem, "output_size_in_bytes", 0) / 2**30, 3),
            "alias_gb": round(getattr(mem, "alias_size_in_bytes", 0) / 2**30, 3),
        }
    except Exception:  # pragma: no cover - backend-dependent
        per_dev_bytes, mem_detail = 0, {}
    # trip-count-aware stats from the optimized per-device HLO (see
    # hlo_stats docstring — raw cost_analysis counts loop bodies once)
    agg = hlo_stats.aggregate(compiled.as_text())
    return {
        "flops_per_device": agg["dot_flops"],
        "hlo_bytes_per_device": agg["traffic"],
        "collective_bytes_per_device": agg["coll_bytes"],
        "collectives": agg["colls"],
        "raw_cost_flops_body_once": float(cost.get("flops", 0.0)),
        "bytes_per_device_gb": round(per_dev_bytes / 2**30, 3),
        "memory_detail": mem_detail,
        "devices": n_dev,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="enable the §Perf optimized variants")
    ap.add_argument("--opt-flags", default=",".join(OPT2_FLAGS),
                    help="comma list of optimization switches")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    todo = cells() if args.all else [(args.arch, args.shape)]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in todo:
        tag = "multi" if args.multi_pod else "single"
        if args.opt:
            tag += "_opt"
        try:
            res = lower_cell(arch, shape, mesh, opt=args.opt,
                             opt_flags=tuple(args.opt_flags.split(",")))
            with open(f"{args.out}/{arch}_{shape}_{tag}.json", "w") as f:
                json.dump(res, f, indent=1)
        except Exception as e:  # surface, keep going in --all mode
            failures.append((arch, shape, repr(e)[:200]))
            print(f"[dryrun] FAIL {arch} x {shape}: {e}", file=sys.stderr)
            if not args.all:
                raise
    if failures:
        print(f"[dryrun] {len(failures)} failures: {failures}", file=sys.stderr)
        sys.exit(1)
    print("[dryrun] all cells compiled OK")


if __name__ == "__main__":
    main()
