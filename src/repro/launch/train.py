"""Training driver: config -> data -> pjit train loop -> checkpoints.

Runs real steps on whatever devices exist (CPU smoke, one pod, multi-pod —
same code; the mesh adapts).  Used by examples/train_lm.py for the
end-to-end ~100M-param run and by the integration tests for
checkpoint/restart.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --scale 0.1 --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import clock
from repro.utils.compile_cache import enable_compile_cache
from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.data import SyntheticLM, ByteCorpus
from repro.distributed.partition import param_specs, zero1_specs
from repro.models.lm import LM
from repro.optim import adamw
from repro.optim.schedules import cosine, wsd
from repro.runtime.fault_tolerance import HeartbeatStore, Monitor
from repro.train import TrainState, create, make_train_step, shard_batch
from repro.train.state import abstract_state

__all__ = ["reduce_config", "Trainer", "main"]


def reduce_config(cfg: ModelConfig, scale: float, *,
                  seq_len: int = 256) -> ModelConfig:
    """Shrink an assigned architecture into a CPU-runnable sibling (same
    family, same block structure, fewer/narrower layers)."""
    def s(x, lo=1, mult=1):
        v = max(lo, int(round(x * scale)))
        return -(-v // mult) * mult

    kw: dict = dict(
        num_layers=max(2, int(round(cfg.num_layers * scale))),
        d_model=s(cfg.d_model, 32, 16),
        vocab_size=min(cfg.vocab_size, 2048),
        dtype="float32", param_dtype="float32",
        remat=False, scan_layers=True,
    )
    if cfg.has_attention:
        heads = max(2, int(round(cfg.num_heads * scale)))
        kvh = max(1, min(cfg.num_kv_heads, heads))
        while heads % kvh:
            kvh -= 1
        kw.update(num_heads=heads, num_kv_heads=kvh,
                  head_dim=max(8, kw["d_model"] // heads // 2 * 2),
                  d_ff=s(cfg.d_ff, 64, 16) if cfg.d_ff else 0)
    if cfg.family == "moe":
        kw.update(num_experts=min(cfg.num_experts, 8),
                  experts_per_token=min(cfg.experts_per_token, 2),
                  moe_d_ff=s(cfg.moe_d_ff, 32, 8),
                  dense_residual=cfg.dense_residual,
                  d_ff=s(cfg.d_ff, 64, 16) if cfg.dense_residual else 0,
                  capacity_factor=4.0)
    if cfg.has_ssm:
        kw.update(ssm_state=min(cfg.ssm_state, 32),
                  ssm_headdim=min(cfg.ssm_headdim, 32),
                  ssm_groups=1, conv_width=cfg.conv_width)
        kw["d_model"] = max(64, kw["d_model"])
    if cfg.family == "hybrid":
        kw.update(attn_every=max(2, min(cfg.attn_every, 3)))
    if cfg.frontend:
        kw.update(frontend=cfg.frontend,
                  frontend_len=min(cfg.frontend_len, seq_len // 4),
                  grid_hw=4, m_rope=cfg.m_rope,
                  mrope_sections=cfg.mrope_sections)
        if cfg.m_rope:
            hd2 = kw["head_dim"] // 2
            kw["mrope_sections"] = (hd2 - 2 * (hd2 // 4), hd2 // 4, hd2 // 4)
    return dataclasses.replace(
        cfg, name=f"{cfg.name}-x{scale}", qk_norm=cfg.qk_norm,
        tie_embeddings=cfg.tie_embeddings, mlp_kind=cfg.mlp_kind,
        scale_embeddings=cfg.scale_embeddings, **kw)


class Trainer:
    """Owns state + jit'd step + checkpointing; the loop a launcher runs."""

    def __init__(self, cfg: ModelConfig, *, mesh=None, microbatches: int = 1,
                 ckpt_dir: Optional[str] = None, save_every: int = 50,
                 lr: float = 3e-4, total_steps: int = 1000,
                 zero1: bool = True, seed: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.lm = LM(cfg)
        sched = wsd(lr, total_steps) if cfg.name.startswith("minicpm") \
            else cosine(lr, total_steps)
        self.opt = adamw(sched)
        self.step_fn = make_train_step(self.lm, self.opt,
                                       microbatches=microbatches)
        self.ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
        self.save_every = save_every
        self.heartbeats = HeartbeatStore()
        self.monitor = Monitor(self.heartbeats)

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            a_state = abstract_state(self.lm, self.opt)
            p_specs = param_specs(a_state.params)
            m_specs = zero1_specs(a_state.params, mesh) if zero1 else p_specs
            specs = TrainState(
                step=P(), params=p_specs,
                opt_state=type(a_state.opt_state)(
                    count=P(), mu=m_specs, nu=m_specs))
            sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs)
            self._jit = jax.jit(self.step_fn, in_shardings=(sh, None),
                                out_shardings=(sh, None),
                                donate_argnums=(0,))
        else:
            self._jit = jax.jit(self.step_fn, donate_argnums=(0,))

        self.state = create(self.lm, self.opt, jax.random.PRNGKey(seed))
        if self.ckpt and self.ckpt.latest_step() is not None:
            self.state = self.ckpt.restore(self.state)
            print(f"resumed from step {int(self.state.step)}")

    def fit(self, data, steps: int, *, log_every: int = 10,
            worker: int = 0) -> dict:
        history = []
        start = int(jax.device_get(self.state.step))
        t0 = clock()
        ctx = jax.sharding.set_mesh(self.mesh) if self.mesh is not None \
            else _nullcontext()
        with ctx:
            for i in range(start, steps):
                batch = jax.tree_util.tree_map(jnp.asarray, data.batch(i))
                if self.mesh is not None:
                    batch = shard_batch(self.mesh, batch)
                self.state, metrics = self._jit(self.state, batch)
                self.heartbeats.post(worker, i)
                if (i + 1) % log_every == 0 or i == start:
                    loss = float(jax.device_get(metrics["loss"]))
                    dt = clock() - t0
                    print(f"step {i+1:5d} loss {loss:.4f} "
                          f"({dt/(i-start+1):.2f}s/step)")
                    history.append({"step": i + 1, "loss": loss})
                if self.ckpt and (i + 1) % self.save_every == 0:
                    self.ckpt.save_async(i + 1, self.state)
        if self.ckpt:
            self.ckpt.wait()
            self.ckpt.save(steps, self.state)
        return {"history": history,
                "final_loss": history[-1]["loss"] if history else None}


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", type=float, default=0.1,
                    help="reduce factor for CPU runs (1.0 = full config)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--corpus", default=None,
                    help="path to a text/binary file (byte-level LM); "
                         "default: synthetic tokens")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.scale != 1.0:
        cfg = reduce_config(cfg, args.scale, seq_len=args.seq)
    print(f"training {cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    if args.corpus:
        with open(args.corpus, "rb") as f:
            blob = f.read()
        cfg = dataclasses.replace(cfg, vocab_size=256)
        data = ByteCorpus(blob, seq_len=args.seq, global_batch=args.batch)
    else:
        data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                           global_batch=args.batch,
                           frontend_len=cfg.frontend_len if cfg.frontend else 0,
                           d_model=cfg.d_model)

    trainer = Trainer(cfg, ckpt_dir=args.ckpt_dir,
                      microbatches=args.microbatches, lr=args.lr,
                      total_steps=args.steps)
    out = trainer.fit(data, args.steps)
    print(f"final loss: {out['final_loss']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
