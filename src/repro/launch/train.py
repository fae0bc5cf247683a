"""Training launcher.

Single-process entry point that scales: on a real multi-host TPU deployment
``jax.distributed.initialize()`` is called (guarded), the same mesh/ruleset
code paths drive 8 or 8192 chips, and the Trainer provides checkpoints,
crash recovery and the straggler watchdog.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \\
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/run1
Overrides: --key=value pairs map onto ModelConfig fields
(e.g. --moe_impl=dense_mask --compute_dtype=float32).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import configs
from repro.data import DataConfig, SyntheticLMData
from repro.dist import sharding as shd
from repro.launch import cache as cache_lib
from repro.launch import mesh as mesh_mod
from repro.models import transformer as T
from repro.optim import schedule
from repro.train import steps as steps_mod
from repro.train.trainer import Trainer, TrainerConfig


def maybe_init_distributed():
    if os.environ.get("REPRO_MULTIHOST") == "1":     # pragma: no cover
        jax.distributed.initialize()


def build(cfg: T.ModelConfig, args, mesh=None):
    ruleset = shd.Ruleset(mesh=mesh, fsdp=args.fsdp) if mesh else None
    sched = schedule.ScheduleConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                                    total_steps=args.steps)
    step = steps_mod.make_train_step(cfg, sched=sched,
                                     accum_steps=args.accum,
                                     compress_grads=args.compress_grads,
                                     error_feedback=args.error_feedback)
    step = jax.jit(step, donate_argnums=(0,))

    def init_fn():
        with shd.use_ruleset(ruleset):
            return steps_mod.init_state(
                jax.random.PRNGKey(args.seed), cfg,
                error_feedback=args.error_feedback).tree()

    def wrapped_step(state, batch):
        with shd.use_ruleset(ruleset):
            return step(state, batch)

    return wrapped_step, init_fn


def frontend_stub(cfg: T.ModelConfig):
    if not cfg.n_frontend_tokens:
        return None

    def make(batch):
        return jnp.zeros((batch, cfg.n_frontend_tokens, cfg.d_model),
                         cfg.dtype)

    return make


def apply_overrides(cfg: T.ModelConfig, overrides: Dict[str, Any]):
    fields = {f.name: f for f in dataclasses.fields(cfg)}
    typed = {}
    for k, v in overrides.items():
        assert k in fields, f"unknown config field {k}"
        t = type(getattr(cfg, k))
        typed[k] = t(v) if t is not type(None) and not isinstance(v, t) else v
    return dataclasses.replace(cfg, **typed)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry the int8 quantization residual in "
                         "TrainState (EF-SGD: bias-free compression); "
                         "implies --compress-grads")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single", "multi"])
    args, extra = ap.parse_known_args(argv)
    cache_lib.use_compile_cache()
    if args.error_feedback:
        args.compress_grads = True

    maybe_init_distributed()
    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    overrides = dict(kv.lstrip("-").split("=", 1) for kv in extra if "=" in kv)
    if overrides:
        cfg = apply_overrides(cfg, overrides)

    mesh = None
    if args.mesh != "none":
        mesh = mesh_mod.make_production_mesh(multi_pod=args.mesh == "multi")

    data = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                      global_batch=args.batch,
                                      seed=args.seed))
    step_fn, init_fn = build(cfg, args, mesh)
    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, checkpoint_every=args.ckpt_every,
                      checkpoint_dir=args.ckpt),
        cfg, data, step_fn, init_fn, frontend_fn=frontend_stub(cfg))
    result = trainer.run()
    for m in result["metrics"]:
        print(f"step {m['step']:5d} loss={m['loss']:.4f} "
              f"nll={m['nll']:.4f} lr={m['lr']:.2e} dt={m['dt']:.3f}s")
    print(f"done: {len(result['metrics'])} logs, "
          f"{result['recoveries']} recoveries, "
          f"{len(result['stragglers'])} stragglers")
    return result


if __name__ == "__main__":
    main()
