import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape × mesh) combination this lowers and
compiles the real step function — train_step for train shapes, prefill /
serve (decode) steps for inference shapes — against ShapeDtypeStruct
stand-ins (no allocation), prints ``memory_analysis()`` /
``cost_analysis()``, and derives the three-term roofline (repro.utils.
roofline).  Results append to a JSONL for EXPERIMENTS.md.

Run:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod --out results/dryrun.jsonl
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod

The XLA_FLAGS line above MUST precede any jax import: jax locks the device
count at first init (which is why only this module — never conftest or the
benches — sees 512 placeholder devices).
"""
import argparse
import functools
import json
import sys
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_configs
from repro.configs.base import ModelConfig
from repro.distributed.partition import (param_specs, data_axes, zero1_specs,
                                         fsdp_specs)
from repro.launch.mesh import make_production_mesh, describe
from repro.launch.shapes import SHAPES, ShapeSpec, applicable
from repro.obs.trace import clock
from repro.models.lm import LM
from repro.optim import adamw
from repro.optim.schedules import wsd, cosine
from repro.train.state import TrainState, abstract_state
from repro.train.step import make_train_step
from repro.utils import roofline

Pytree = Any


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, shardable, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict[str, jax.ShapeDtypeStruct]:
    """Abstract model inputs for one cell.

    train/prefill: {tokens, labels?, frontend_embeds?}; decode: {tokens}
    (the cache is built separately by :func:`cache_specs`)."""
    B, S = shape.global_batch, shape.seq_len
    f = cfg.frontend_len if cfg.frontend else 0
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    s_tok = S - f
    specs = {"tokens": jax.ShapeDtypeStruct((B, s_tok), jnp.int32)}
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, s_tok), jnp.int32)
    if f:
        specs["frontend_embeds"] = jax.ShapeDtypeStruct(
            (B, f, cfg.d_model), cfg.act_dtype)
    return specs


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _maybe(axis: Optional[str], dim: int, mesh) -> Optional[str]:
    """Shard ``dim`` over ``axis`` only when divisible (B=1 etc. replicate)."""
    if axis is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis == "batch":
        axes = data_axes(mesh)
        width = 1
        for a in axes:
            width *= sizes[a]
        if not _div(dim, width):
            return None
        return axes if len(axes) > 1 else axes[0]
    return axis if _div(dim, sizes.get(axis, 0)) else None


def cache_specs(cfg: ModelConfig, mesh, abstract_cache: Pytree) -> Pytree:
    """PartitionSpec tree for a decode cache.

    K/V (layers|groups, B, hk, S, hd): batch over data, head_dim over model
    (every assigned arch has head_dim % 16 == 0; kv_heads often isn't).
    SSM state (L, B, H, P, N): heads over model.  Conv (L, B, w-1, C):
    channels over model.
    """
    def spec_for(path, leaf):
        name = jax.tree_util.keystr(path)
        shp = leaf.shape
        if "'k'" in name or "'v'" in name:
            return P(None, _maybe("batch", shp[1], mesh), None, None,
                     _maybe("model", shp[4], mesh))
        if "conv" in name:
            return P(None, _maybe("batch", shp[1], mesh), None,
                     _maybe("model", shp[3], mesh))
        if "ssm" in name:
            return P(None, _maybe("batch", shp[1], mesh),
                     _maybe("model", shp[2], mesh), None, None)
        return P()  # cur_len

    return jax.tree_util.tree_map_with_path(spec_for, abstract_cache)


# ---------------------------------------------------------------------------
# step builders (one per shape kind)
# ---------------------------------------------------------------------------

def build_train(cfg: ModelConfig, mesh, *, microbatches: int = 1,
                zero1: bool = True, fsdp: Optional[bool] = None):
    lm = LM(cfg)
    sched = wsd(3e-4, 100_000) if cfg.name == "minicpm-2b" \
        else cosine(3e-4, 100_000)
    moment_dtype = jnp.bfloat16 if cfg.param_count() > 1e11 else jnp.float32
    opt = adamw(sched, moment_dtype=moment_dtype)
    step_fn = make_train_step(lm, opt, microbatches=microbatches)

    state = abstract_state(lm, opt)
    if fsdp is None:
        # auto: params that exceed ~8 GiB/device under TP-only sharding
        # must also shard over data (ZeRO-3); arctic-480b is the only one
        fsdp = cfg.param_count() * 2 / 16 > 8 * (1 << 30)
    p_specs = fsdp_specs(state.params, mesh, cfg) if fsdp \
        else param_specs(state.params, cfg)
    m_specs = zero1_specs(state.params, mesh, cfg) if (zero1 or fsdp) \
        else p_specs
    state_specs = TrainState(
        step=P(), params=p_specs,
        opt_state=type(state.opt_state)(count=P(), mu=m_specs, nu=m_specs))
    state_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), state_specs)

    def batch_sharding(leaf):
        b = _maybe("batch", leaf.shape[0], mesh)
        return NamedSharding(mesh, P(b, *(None,) * (leaf.ndim - 1)))

    inputs = input_specs(cfg, SHAPES["train_4k"])
    batch_sh = jax.tree_util.tree_map(batch_sharding, inputs)

    jitted = jax.jit(step_fn,
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,))
    return jitted, (state, inputs)


def build_prefill(cfg: ModelConfig, mesh, shape: ShapeSpec):
    lm = LM(cfg)
    a_params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(a_params, cfg))

    inputs = input_specs(cfg, shape)

    def batch_sharding(leaf):
        b = _maybe("batch", leaf.shape[0], mesh)
        return NamedSharding(mesh, P(b, *(None,) * (leaf.ndim - 1)))

    in_sh = jax.tree_util.tree_map(batch_sharding, inputs)

    def prefill_step(params, batch):
        return lm.prefill(params, batch["tokens"],
                          batch.get("frontend_embeds"))

    jitted = jax.jit(prefill_step, in_shardings=(p_sh, in_sh))
    return jitted, (a_params, inputs)


def build_decode(cfg: ModelConfig, mesh, shape: ShapeSpec):
    lm = LM(cfg)
    B, S = shape.global_batch, shape.seq_len
    a_params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(a_params, cfg))
    a_cache = jax.eval_shape(
        functools.partial(lm.init_cache, B, S, dtype=cfg.act_dtype))
    c_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), cache_specs(cfg, mesh, a_cache))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t_sh = NamedSharding(mesh, P(_maybe("batch", B, mesh), None))

    def serve_step(params, cache, tokens):
        return lm.decode_step(params, cache, tokens)

    jitted = jax.jit(serve_step, in_shardings=(p_sh, c_sh, t_sh),
                     out_shardings=(None, c_sh), donate_argnums=(1,))
    return jitted, (a_params, a_cache, tok)


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh, *, verbose: bool = True,
             microbatches: int = 1) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    t0 = clock()
    if shape.kind == "train":
        jitted, args = build_train(cfg, mesh, microbatches=microbatches)
    elif shape.kind == "prefill":
        jitted, args = build_prefill(cfg, mesh, shape)
    else:
        jitted, args = build_decode(cfg, mesh, shape)

    with jax.sharding.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = clock() - t0
        t0 = clock()
        compiled = lowered.compile()
        t_compile = clock() - t0

    mem = compiled.memory_analysis()
    n_chips = mesh.devices.size
    n_tokens = (shape.global_batch * shape.seq_len
                if shape.kind != "decode" else shape.global_batch)
    terms = roofline.analyze(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        n_chips=n_chips, cfg=cfg, n_tokens=n_tokens,
        training=(shape.kind == "train"))

    rec = terms.to_json()
    rec.update({
        "status": "ok", "kind": shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "arg_bytes_per_dev": getattr(mem, "argument_size_in_bytes", None),
        "temp_bytes_per_dev": getattr(mem, "temp_size_in_bytes", None),
        "out_bytes_per_dev": getattr(mem, "output_size_in_bytes", None),
        "alias_bytes_per_dev": getattr(mem, "alias_size_in_bytes", None),
    })
    if verbose:
        gb = 1 << 30
        arg = (rec["arg_bytes_per_dev"] or 0) / gb
        tmp = (rec["temp_bytes_per_dev"] or 0) / gb
        print(f"[{arch} × {shape_name} × {mesh_name}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s | "
              f"args {arg:.2f} GiB/dev temp {tmp:.2f} GiB/dev | "
              f"t_comp {terms.t_compute*1e3:.2f}ms t_mem "
              f"{terms.t_memory*1e3:.2f}ms t_coll "
              f"{terms.t_collective*1e3:.2f}ms -> {terms.dominant}-bound, "
              f"roofline {terms.roofline_fraction:.2%}")
    return rec


# ---------------------------------------------------------------------------
# depth-corrected roofline (scan bodies are counted ONCE by cost_analysis,
# so scanned-program flops/bytes/collectives underreport by ~num_layers;
# two shallow UNROLLED probes give exact per-layer costs to extrapolate)
# ---------------------------------------------------------------------------

def _probe_depths(cfg: ModelConfig) -> tuple[int, int]:
    if cfg.family == "hybrid":
        return cfg.attn_every, 2 * cfg.attn_every   # unit = one shared-attn group
    return 2, 4


def _probe_cost(cfg: ModelConfig, shape: ShapeSpec, mesh, depth: int,
                microbatches: int = 1) -> dict:
    import dataclasses as _dc
    sub = _dc.replace(cfg, name=f"{cfg.name}-probe{depth}",
                      num_layers=depth, scan_layers=False)
    if shape.kind == "train":
        jitted, args = build_train(sub, mesh, microbatches=microbatches)
    elif shape.kind == "prefill":
        jitted, args = build_prefill(sub, mesh, shape)
    else:
        jitted, args = build_decode(sub, mesh, shape)
    with jax.sharding.set_mesh(mesh):
        compiled = jitted.lower(*args).compile()
    ca = compiled.cost_analysis()
    if not isinstance(ca, dict):
        ca = ca[0]
    from repro.utils import hlo as hlo_mod
    coll = hlo_mod.collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll.get("total", 0)),
            "coll_breakdown": {k: v for k, v in coll.items()
                               if k != "total"}}


def corrected_terms(arch: str, shape_name: str, mesh, *,
                    microbatches: int = 1,
                    cfg_override: Optional[ModelConfig] = None) -> dict:
    """Depth-extrapolated roofline terms: cost(L) = fixed + L*per_layer,
    measured at two shallow unrolled depths.  The hybrid family's unit is
    one (attn_every mambas + shared attn) group."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}
    d1, d2 = _probe_depths(cfg)
    c1 = _probe_cost(cfg, shape, mesh, d1, microbatches)
    c2 = _probe_cost(cfg, shape, mesh, d2, microbatches)
    L = cfg.num_layers

    def extrap(key):
        per = (c2[key] - c1[key]) / (d2 - d1)
        fixed = c1[key] - d1 * per
        return max(fixed + L * per, 0.0)

    flops, hbm, coll = extrap("flops"), extrap("bytes"), extrap("coll")
    hw = roofline.TPU_V5E
    n_chips = mesh.devices.size
    n_tokens = (shape.global_batch * shape.seq_len
                if shape.kind != "decode" else shape.global_batch)
    mf = roofline.model_flops(cfg, n_tokens,
                              training=(shape.kind == "train"))
    t_c, t_m, t_x = flops / hw.peak_flops, hbm / hw.hbm_bw, coll / hw.ici_bw
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "status": "ok", "method": f"unrolled-probe d={d1},{d2} extrapolated",
        "flops_per_chip": flops, "hbm_bytes_per_chip": hbm,
        "coll_bytes_per_chip": coll,
        "coll_breakdown_probe": c2["coll_breakdown"],
        "t_compute": t_c, "t_memory": t_m, "t_collective": t_x,
        "dominant": dom,
        "step_time": max(t_c, t_m, t_x),
        "roofline_fraction": t_c / max(t_c, t_m, t_x, 1e-30),
        "model_flops_total": mf,
        "useful_ratio": (mf / n_chips) / flops if flops else 0.0,
        "mfu_bound": (mf / n_chips) / (max(t_c, t_m, t_x) * hw.peak_flops)
        if max(t_c, t_m, t_x) else 0.0,
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None,
                    help="architecture id (default: all assigned)")
    ap.add_argument("--shape", default=None,
                    help="shape name (default: all four)")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--all", action="store_true",
                    help="all (arch × shape) cells")
    ap.add_argument("--corrected", action="store_true",
                    help="depth-extrapolated roofline (unrolled probes) "
                         "instead of the scanned-program compile")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else [
        a for a in list_configs() if not a.startswith("euroben")]
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        print(f"=== {describe(mesh)} ===")
        for arch in archs:
            for shape in shapes:
                try:
                    if args.corrected:
                        rec = corrected_terms(arch, shape, mesh,
                                              microbatches=args.microbatches)
                        if rec.get("status") == "ok":
                            print(f"[{arch} × {shape}] corrected: "
                                  f"t_comp {rec['t_compute']*1e3:.1f}ms "
                                  f"t_mem {rec['t_memory']*1e3:.1f}ms "
                                  f"t_coll {rec['t_collective']*1e3:.1f}ms "
                                  f"-> {rec['dominant']}-bound, roofline "
                                  f"{rec['roofline_fraction']:.2%}, mfu<= "
                                  f"{rec['mfu_bound']:.2%}")
                    else:
                        rec = run_cell(arch, shape, mesh,
                                       microbatches=args.microbatches)
                except Exception as e:  # a failing cell is a bug: report it
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multipod" if multi else "pod",
                           "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
                    failures.append(rec)
                    print(f"[{arch} × {shape}] FAILED: {rec['error'][:200]}")
                if rec.get("status") == "skipped":
                    print(f"[{arch} × {shape}] skipped: {rec['reason'][:80]}")
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    if failures:
        print(f"\n{len(failures)} cells FAILED")
        return 1
    print("\nall requested cells compiled")
    return 0


if __name__ == "__main__":
    sys.exit(main())
