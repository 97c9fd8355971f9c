"""Attention suite — chip flash vs sequence-parallel ring over an L sweep,
plus the block-sparse mask-density sweep (DESIGN.md §12).

The paper's headline table re-runs one program under O2/O3 with the core
count as the only knob; this suite replays that for the hot path every
model config shares: causal GQA attention.  Each sequence length is timed
twice —

    chip   use_level(O2): the chip kernel plane (pallas on TPU, the
           chunked/oracle XLA forms elsewhere)
    ring   use_level(O3) on a (ring, 1) data mesh: the same dispatch
           retargets to the sequence-parallel ring variant
           (repro.distributed.attention, DESIGN.md §10)

— recording tokens/s and the variant the registry actually selected, so
the ``--json-out`` trajectory shows both rows per L and scaling
regressions in either stay visible.  On the CPU container the fake host
devices share one socket, so (exactly as for the scaling sweep) the
artefact is the per-shape trajectory and selection, not absolute speedups.

The density sweep times the tile-skipping kernel against its own
all-tiles-launched form (``dense_masked_layout`` — the dense grid's work
for a rich mask, in the same kernel so the A/B isolates tile skipping) at
block-pattern masks of ~6/12/25/50% live tiles, recording tokens/s, the
speedup, and GFLOP/s-skipped (the avoided-FLOP rate: how much dense work
per wall-second the skipped tiles would have cost).  A causal-parity pair
rides along: the row-extent banded grid vs the legacy ``pl.when``
full-grid causal kernel.  Both run on the registry's plane: compiled on a
TPU, interpreted when ``REPRO_KERNELS=interpret`` asks for it, and left out
(with a printed reason) on the XLA plane, which has no such kernel.

    PYTHONPATH=src python -m benchmarks.run --only attention
    PYTHONPATH=src python -m benchmarks.run --only attention --json-out a.json
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import print_table, time_fn

#: problem shape: batch, q heads, kv heads (GQA 4:2), head dim.
B, H, HK, D = 2, 4, 2, 64

#: sequence lengths swept (every entry divisible by 2 * ring for the
#: zig-zag causal layout on an 8-wide ring).
LS = (512, 1024)
LS_FULL = (512, 1024, 2048, 4096)


def _qkv(L: int):
    import jax.numpy as jnp

    rng = np.random.default_rng(L)
    q = jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, HK, L, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, HK, L, D)), jnp.float32)
    return q, k, v


#: density sweep shape: sequence length, tile size, target live fractions.
SWEEP_L, SWEEP_BLOCK = 512, 64
SWEEP_DENSITIES = (0.125, 0.25, 0.5)   # 1/nk floor: every Q row stays live


def _block_pattern(nq: int, nk: int, density: float, seed: int = 0):
    """A random tile pattern with exactly ``round(density * nq * nk)`` live
    tiles, the diagonal forced live so every Q row attends somewhere."""
    rng = np.random.default_rng(seed)
    n_live = max(int(round(density * nq * nk)), nq)
    pat = np.zeros((nq, nk), bool)
    pat[np.arange(nq), np.arange(nq) * nk // nq] = True
    rest = np.flatnonzero(~pat.ravel())
    extra = rng.choice(rest, size=n_live - int(pat.sum()), replace=False)
    pat.ravel()[extra] = True
    return pat


def density_sweep(plane: str) -> list[dict]:
    """Blocksparse vs dense-masked A/B per mask density + causal parity,
    on the ``pallas`` or ``interpret`` plane."""
    import jax

    from repro.kernels import flash_attention as fa_k
    from repro.sparse.maskcompiler import (MaskSpec, compile_layout,
                                           dense_masked_layout)

    interpret = plane == "interpret"
    L, blk = SWEEP_L, SWEEP_BLOCK
    nq = nk = L // blk
    q, k, v = _qkv(L)
    flops_dense = 4.0 * B * H * L * L * D          # QK^T + PV, dense

    rows: list[dict] = []
    for target in SWEEP_DENSITIES:
        spec = MaskSpec.from_block_mask(_block_pattern(nq, nk, target), blk)
        lay = compile_layout(spec, L, L, blk, blk)
        base = dense_masked_layout(spec, L, L, blk, blk)
        run_bs = jax.jit(lambda q, k, v, lay=lay: fa_k.flash_attention_tiles(
            q, k, v, lay, interpret=interpret))
        run_dm = jax.jit(lambda q, k, v, lay=base: fa_k.flash_attention_tiles(
            q, k, v, lay, interpret=interpret))
        t_bs = time_fn(run_bs, q, k, v, warmup=1, iters=3)
        t_dm = time_fn(run_dm, q, k, v, warmup=1, iters=3)
        rows.append({
            "L": L, "mode": "density", "density": round(lay.density, 4),
            "live_tiles": lay.ntiles, "tiles": nq * nk,
            "seconds": round(t_bs, 6),
            "seconds_dense_masked": round(t_dm, 6),
            "speedup": round(t_dm / t_bs, 3),
            "tokens_per_s": round(B * L / t_bs, 1),
            "gflops_skipped": round(
                flops_dense * (1.0 - lay.density) / t_bs / 1e9, 3),
        })

    # causal parity: banded row extents vs the legacy pl.when full grid
    run_ext = jax.jit(lambda q, k, v: fa_k.flash_attention(
        q, k, v, causal=True, block_q=blk, block_k=blk, interpret=interpret))
    run_when = jax.jit(lambda q, k, v: fa_k.flash_attention(
        q, k, v, causal=True, block_q=blk, block_k=blk, row_extents=False,
        interpret=interpret))
    t_ext = time_fn(run_ext, q, k, v, warmup=1, iters=3)
    t_when = time_fn(run_when, q, k, v, warmup=1, iters=3)
    causal_density = (nq + 1) / (2 * nk)
    rows.append({
        "L": L, "mode": "causal_parity", "density": round(causal_density, 4),
        "live_tiles": nq * (nq + 1) // 2, "tiles": nq * nk,
        "seconds": round(t_ext, 6),
        "seconds_dense_masked": round(t_when, 6),
        "speedup": round(t_when / t_ext, 3),
        "tokens_per_s": round(B * L / t_ext, 1),
        "gflops_skipped": round(
            flops_dense * (1.0 - causal_density) / t_ext / 1e9, 3),
    })
    return rows


def main(full: bool = False) -> list[dict]:
    import jax

    from repro.core import ExecLevel, registry, use_level
    from repro.distributed.collectives import ring_plan
    from repro.kernels import ops

    # largest power-of-two ring the devices allow: 2*ring then divides
    # every swept L (multiples of 512), so the ring rows really time the
    # ring variant instead of silently degrading to chip
    ring = 1 << (min(jax.device_count(), 8).bit_length() - 1)
    mesh = None
    if ring > 1:
        mesh = jax.make_mesh((ring, 1), ("data", "model"),
                             (jax.sharding.AxisType.Auto,) * 2,
                             devices=jax.devices()[:ring])
        ring = ring_plan(mesh).size
    else:
        print("attention suite: 1 device visible — ring rows degrade to "
              "chip (run under XLA_FLAGS=--xla_force_host_platform_"
              "device_count=8 for a real ring)")

    modes = [("chip", lambda: use_level(ExecLevel.O2), 1)]
    if mesh is not None:
        modes.append(("ring", lambda: use_level(ExecLevel.O3, mesh), ring))

    rows: list[dict] = []
    for L in (LS_FULL if full else LS):
        q, k, v = _qkv(L)
        for mode, ctx, width in modes:
            with ctx():
                sel = registry.select("flash_attention", q, k, v,
                                      causal=True).name
                t = time_fn(lambda: ops.flash_attention(q, k, v, causal=True),
                            warmup=1, iters=3)
            rows.append({
                "L": L, "mode": mode, "variant": sel, "ring": width,
                "seconds": round(t, 6),
                "tokens_per_s": round(B * L / t, 1),
            })
    print_table("attention (chip flash vs sequence-parallel ring, causal "
                f"GQA {H}:{HK} heads, d={D})", rows,
                ["L", "mode", "variant", "ring", "seconds", "tokens_per_s"])

    plane = registry.resolve_backend()
    if plane == "xla":
        print("attention mask-density sweep: left out — it times the Pallas "
              "tile kernels, which the xla plane does not have (run on a TPU "
              "or under REPRO_KERNELS=interpret)")
        return rows
    sweep = density_sweep(plane)
    print_table("attention mask-density sweep (blocksparse vs dense-masked, "
                f"L={SWEEP_L}, {SWEEP_BLOCK}x{SWEEP_BLOCK} tiles, {plane} "
                "plane)", sweep,
                ["L", "mode", "density", "live_tiles", "tiles", "seconds",
                 "seconds_dense_masked", "speedup", "tokens_per_s",
                 "gflops_skipped"])
    return rows + sweep


if __name__ == "__main__":
    main()
