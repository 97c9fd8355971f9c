"""Serving driver: load (or init) a model, run batched generation.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --scale 0.08 --batch 4 --prompt-len 32 --new-tokens 16

``--opt-level O3`` (or the ``ARBB_OPT_LEVEL`` env var) builds the engine
under an ambient mesh: the prefill path then shards long prompts over the
sequence-parallel ring (DESIGN.md §10) while the decode loop stays
chip-local — the engine pins the level at construction, exactly as it pins
the kernel plane.
"""
from __future__ import annotations

import argparse
import contextlib
import sys

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.core import ExecLevel, use_level
from repro.launch.train import reduce_config
from repro.models.lm import LM
from repro.obs.trace import clock
from repro.utils.compile_cache import enable_compile_cache
from repro.serve import Engine, SamplingParams


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--opt-level", default=None, choices=["O2", "O3", "O4"],
                    help="execution level for the engine: O3/O4 shard the "
                         "prefill sequence over the ring (default: the "
                         "ambient level / ARBB_OPT_LEVEL)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.scale != 1.0:
        cfg = reduce_config(cfg, args.scale)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        ckpt = Checkpointer(args.ckpt_dir)
        from repro.optim import adamw
        from repro.optim.schedules import constant
        from repro.train.state import create
        state = create(lm, adamw(constant(1e-4)), jax.random.PRNGKey(0))
        params = ckpt.restore(state).params
        print(f"loaded checkpoint step {ckpt.latest_step()}")

    sp = SamplingParams(greedy=args.temperature == 0.0,
                        temperature=max(args.temperature, 1e-6))
    max_len = args.max_len or (args.prompt_len + args.new_tokens + 8)
    level_ctx = (use_level(ExecLevel[args.opt_level]) if args.opt_level
                 else contextlib.nullcontext())
    with level_ctx:
        # the engine pins the ambient level/mesh: O3/O4 prefill rides the
        # sequence-parallel ring on every generate() (DESIGN.md §10)
        engine = Engine(lm, params, max_len=max_len, sampling=sp)
    if engine.active_level.mesh is not None:
        from repro.launch.mesh import describe
        print(f"engine level {engine.active_level.level.name} on "
              f"{describe(engine.active_level.mesh)}")

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    fe = None
    if cfg.frontend:
        fe = jnp.zeros((args.batch, cfg.frontend_len, cfg.d_model),
                       jnp.float32)
    t0 = clock()
    out = engine.generate(prompts, max_new_tokens=args.new_tokens,
                          frontend_embeds=fe)
    dt = clock() - t0
    toks = args.batch * args.new_tokens
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s incl. compile)")
    print("first row:", out[0].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
