import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"      # placeholders, never a chip

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with NO device allocation (ShapeDtypeStruct inputs).

For each cell this prints/records:
  * ``compiled.memory_analysis()``  — proves the per-device footprint fits;
  * ``compiled.cost_analysis()``    — HLO FLOPs/bytes for §Roofline;
  * collective bytes parsed from the HLO text — the roofline's third term.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh both --out artifacts/
Perf-iteration knobs (EXPERIMENTS.md §Perf): --kv-dtype, --moe-impl,
--no-remat, --no-fsdp, --flash.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs import shapes as shapes_mod
from repro.core import hlo_analysis, roofline
from repro.dist import sharding as shd
from repro.launch import mesh as mesh_mod
from repro.models import transformer as T
from repro.optim import adamw
from repro.serve import engine as serve_engine
from repro.train import steps as train_steps


# ----------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input.
# ----------------------------------------------------------------------------

def input_specs(cfg: T.ModelConfig, shape: shapes_mod.ShapeSpec,
                kv_dtype=None) -> Dict[str, Any]:
    """ShapeDtypeStructs for one cell (weak-type-correct, shardable, no
    allocation)."""
    b = shape.global_batch
    kv_dtype = kv_dtype or cfg.dtype
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
        }
        if cfg.n_frontend_tokens:
            specs["frontend"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
        return specs
    if shape.kind == "prefill":
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
            "caches": jax.eval_shape(
                lambda: T.init_caches(cfg, b, shape.seq_len, dtype=kv_dtype)),
        }
        if cfg.n_frontend_tokens:
            specs["frontend"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
        return specs
    # decode: one new token against a seq_len cache.
    specs = {
        "last_tokens": jax.ShapeDtypeStruct((b,), jnp.int32),
        "caches": jax.eval_shape(
            lambda: T.init_caches(cfg, b, shape.seq_len, dtype=kv_dtype)),
    }
    if cfg.n_frontend_tokens:
        specs["cross_kv"] = jax.ShapeDtypeStruct(
            (b, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
    return specs


# ----------------------------------------------------------------------------
# Sharding construction
# ----------------------------------------------------------------------------

def state_shardings(cfg: T.ModelConfig, ruleset: shd.Ruleset):
    shapes = jax.eval_shape(
        lambda k: train_steps.init_state(k, cfg).tree(),
        jax.random.PRNGKey(0))
    mesh = ruleset.mesh

    def leaf_spec(path, leaf):
        names = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path)
        return jax.sharding.NamedSharding(
            mesh, shd.param_spec(names, leaf.shape, ruleset))

    return jax.tree_util.tree_map_with_path(leaf_spec, shapes), shapes


def batch_shardings(specs, ruleset: shd.Ruleset, shape_kind: str):
    mesh = ruleset.mesh

    def spec_for(path, leaf):
        names = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path)
        key = names[0] if names else ""
        dims: list = [None] * len(leaf.shape)
        if key in ("tokens", "labels", "last_tokens", "frontend", "cross_kv"):
            dims[0] = "batch"
        elif key == "caches":
            leafname = names[-1]
            if leafname in ("k", "v"):
                # (periods, b, cache_len, kvh, hd)
                dims = [None, "batch", "cache_seq", "kv_heads", None]
            elif leafname == "conv":
                dims = [None, "batch", None, "ssm_heads", None]
            elif leafname == "ssm":
                dims = [None, "batch", "ssm_heads", None, None]
            else:                       # index
                dims = [None] * len(leaf.shape)
        return jax.sharding.NamedSharding(
            mesh, ruleset.spec(dims, leaf.shape))

    return jax.tree_util.tree_map_with_path(spec_for, specs)


# ----------------------------------------------------------------------------
# Cell lowering
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    skipped: bool = False
    reason: str = ""
    compile_s: float = 0.0
    memory: Optional[Dict[str, float]] = None
    cost: Optional[Dict[str, float]] = None
    collective_bytes: float = 0.0
    collective_detail: Optional[Dict[str, int]] = None
    roofline: Optional[Dict[str, Any]] = None


def prepare_cfg(cfg: T.ModelConfig, args) -> T.ModelConfig:
    upd: Dict[str, Any] = {"compute_dtype": "bfloat16",
                           "scan_layers": True}
    upd["remat"] = not args.no_remat
    if args.moe_impl:
        upd["moe_impl"] = args.moe_impl
    if args.flash:
        upd["use_flash"] = True
    if args.expand_kv:
        upd["expand_kv"] = True
    if args.bf16_probs:
        upd["attn_probs_fp32"] = False
    if args.remat_policy:
        upd["remat_policy"] = args.remat_policy
    if args.capacity_factor:
        upd["moe_capacity_factor"] = args.capacity_factor
    return dataclasses.replace(cfg, **upd)


def lower_cell(arch_id: str, shape_name: str, mesh, args) -> CellResult:
    mesh_name = mesh_mod.describe(mesh)
    ok, why = shapes_mod.runnable(arch_id, shape_name)
    if not ok:
        return CellResult(arch_id, shape_name, mesh_name, ok=True,
                          skipped=True, reason=why)
    cfg = prepare_cfg(configs.get_config(arch_id), args)
    shape = shapes_mod.SHAPES[shape_name]
    rules = {}
    if shape.name == "long_500k":
        # Sequence parallelism: the 500k cache shards over the data axis.
        rules["cache_seq"] = "data"
    if args.replicate_experts:
        # EP-off: expert weights replicate; MoE dispatch goes chip-local
        # (trades HBM for the all-to-all/all-reduce dispatch traffic).
        rules["experts"] = None
    if args.shard_cache_seq:
        # Sequence-parallel KV cache over the model axis: the fix for GQA
        # archs whose kv_heads don't divide the axis (attention runs with
        # partial-softmax collectives instead of a replicated cache).
        rules["cache_seq"] = args.shard_cache_seq
    ruleset = shd.Ruleset(rules=rules, mesh=mesh, fsdp=not args.no_fsdp
                          and shape.kind == "train")
    kv_dtype = jnp.int8 if args.kv_dtype == "int8" else cfg.dtype
    specs = input_specs(cfg, shape, kv_dtype=kv_dtype)
    chips = int(np.prod(list(mesh.shape.values())))

    t0 = time.time()
    with mesh, shd.use_ruleset(ruleset):
        if shape.kind == "train":
            step = train_steps.make_train_step(cfg,
                                               accum_steps=args.accum)
            state_sh, state_shapes = state_shardings(cfg, ruleset)
            batch_sh = batch_shardings(specs, ruleset, shape.kind)
            lowered = jax.jit(
                step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,)).lower(state_shapes, specs)
            mode = "train"
            cache_len = 0
        elif shape.kind == "prefill":
            def prefill_fn(params, tokens, caches, frontend=None):
                return serve_engine.prefill(params, cfg, tokens, caches,
                                            frontend_embeds=frontend)

            serve_dtype = jnp.bfloat16 if args.serve_params_bf16 else None
            param_sh, param_shapes = _param_only_shardings(cfg, ruleset,
                                                           dtype=serve_dtype)
            batch_sh = batch_shardings(specs, ruleset, shape.kind)
            in_sh = (param_sh, batch_sh["tokens"], batch_sh["caches"])
            lower_args = [param_shapes, specs["tokens"], specs["caches"]]
            if "frontend" in specs:
                in_sh = in_sh + (batch_sh["frontend"],)
                lower_args.append(specs["frontend"])
            lowered = jax.jit(
                prefill_fn, in_shardings=in_sh,
                out_shardings=None).lower(*lower_args)
            mode = "prefill"
            cache_len = 0
        else:
            def serve_fn(params, last_tokens, caches, cross_kv=None):
                logits, new_caches, _ = T.forward(
                    params, cfg, last_tokens[:, None], caches=caches,
                    cross_kv=cross_kv)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return nxt, new_caches

            serve_dtype = jnp.bfloat16 if args.serve_params_bf16 else None
            param_sh, param_shapes = _param_only_shardings(cfg, ruleset,
                                                           dtype=serve_dtype)
            batch_sh = batch_shardings(specs, ruleset, shape.kind)
            in_sh = (param_sh, batch_sh["last_tokens"], batch_sh["caches"])
            lower_args = [param_shapes, specs["last_tokens"], specs["caches"]]
            if "cross_kv" in specs:
                in_sh = in_sh + (batch_sh["cross_kv"],)
                lower_args.append(specs["cross_kv"])
            lowered = jax.jit(
                serve_fn, in_shardings=in_sh, out_shardings=None,
                donate_argnums=(2,)).lower(*lower_args)
            mode = "decode"
            cache_len = shape.seq_len

        compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = hlo_analysis.memory_analysis_bytes(compiled)
    cost = hlo_analysis.cost_analysis_terms(compiled)
    text = compiled.as_text()
    stats = hlo_analysis.collective_stats(text)
    seq_for_flops = 1 if shape.kind == "decode" else shape.seq_len
    mf = T.model_flops(cfg, shape.global_batch, seq_for_flops,
                       mode="train" if mode == "train" else "inference",
                       cache_len=cache_len)
    terms = roofline.terms_from_compiled(
        arch_id, shape_name, mesh_name, chips, compiled, mf,
        hlo_text=text, scan_trips=cfg.periods)
    return CellResult(
        arch=arch_id, shape=shape_name, mesh=mesh_name, ok=True,
        compile_s=compile_s, memory=mem, cost=cost,
        collective_bytes=float(stats.total_bytes),
        collective_detail=stats.bytes_by_kind,
        roofline=terms.to_dict())


def _param_only_shardings(cfg: T.ModelConfig, ruleset: shd.Ruleset,
                          dtype=None):
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    if dtype is not None:
        shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, dtype if l.dtype == jnp.float32 else l.dtype),
            shapes)
    mesh = ruleset.mesh

    def leaf_spec(path, leaf):
        names = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path)
        # Serving keeps params TP-sharded only (no FSDP gather per token).
        serve_rules = shd.Ruleset(rules=ruleset.rules, mesh=mesh, fsdp=False)
        return jax.sharding.NamedSharding(
            mesh, shd.param_spec(names, leaf.shape, serve_rules))

    return jax.tree_util.tree_map_with_path(leaf_spec, shapes), shapes


# ----------------------------------------------------------------------------
# Driver
# ----------------------------------------------------------------------------

def run(args) -> int:
    mesh_kinds = (["single", "multi"] if args.mesh == "both"
                  else [args.mesh])
    arch_ids = ([configs.canonical_id(a) for a in configs.list_archs()]
                if args.arch == "all" else [args.arch])
    shape_names = (list(shapes_mod.SHAPES) if args.shape == "all"
                   else [args.shape])
    results = []
    failures = 0
    for mesh_kind in mesh_kinds:
        mesh = mesh_mod.make_production_mesh(multi_pod=(mesh_kind == "multi"))
        for arch_id in arch_ids:
            for shape_name in shape_names:
                tag = f"{arch_id} x {shape_name} @ {mesh_mod.describe(mesh)}"
                try:
                    res = lower_cell(arch_id, shape_name, mesh, args)
                except Exception as e:  # noqa: BLE001 — report and continue
                    traceback.print_exc()
                    res = CellResult(arch_id, shape_name,
                                     mesh_mod.describe(mesh), ok=False,
                                     reason=f"{type(e).__name__}: {e}")
                    failures += 1
                results.append(res)
                if res.skipped:
                    print(f"[skip] {tag}: {res.reason}", flush=True)
                elif res.ok:
                    r = res.roofline
                    print(f"[ok]   {tag}: compile={res.compile_s:.1f}s "
                          f"flops/chip={res.cost['flops']:.3e} "
                          f"bytes/chip={res.cost['bytes']:.3e} "
                          f"coll={res.collective_bytes:.3e} "
                          f"dominant={r['dominant']} "
                          f"frac={r['roofline_fraction']:.3f}", flush=True)
                    if args.verbose:
                        print(f"       memory_analysis: {res.memory}")
                        print(f"       collectives: {res.collective_detail}")
                else:
                    print(f"[FAIL] {tag}: {res.reason}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump([dataclasses.asdict(r) for r in results], f, indent=1)
        print(f"wrote {args.out}")
    print(f"{sum(1 for r in results if r.ok and not r.skipped)} ok, "
          f"{sum(1 for r in results if r.skipped)} skipped, "
          f"{failures} failed")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="")
    ap.add_argument("--verbose", action="store_true")
    # Perf-iteration knobs (§Perf)
    ap.add_argument("--kv-dtype", default="", choices=["", "int8"])
    ap.add_argument("--moe-impl", default="",
                    choices=["", "capacity", "dense_mask"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--flash", action="store_true")
    ap.add_argument("--expand-kv", action="store_true")
    ap.add_argument("--bf16-probs", action="store_true")
    ap.add_argument("--remat-policy", default="", choices=["", "full", "dots"])
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches")
    ap.add_argument("--replicate-experts", action="store_true")
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--shard-cache-seq", default="",
                    choices=["", "model", "data"])
    ap.add_argument("--serve-params-bf16", action="store_true")
    sys.exit(run(ap.parse_args()))


if __name__ == "__main__":
    main()
