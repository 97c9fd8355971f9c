"""Sequence-parallel (ring) attention: the mesh-scoped flash variant
(DESIGN.md §10).

The beyond-paper flash kernel was the last registry op still pinned to one
chip: every model config in ``repro.models`` runs attention on its hot path,
but under ``use_level(O3/O4)`` the sequence stayed replicated while the
four paper kernels already retargeted to shard_map formulations.  This
module is the missing rung — the RapidMind portability lesson (PAPERS.md)
applied once more: the *same* operator formulation must scale past one
device without forking call sites.

Partitioning: Q, K and V shard over the **sequence** dimension on the ring
axes (pod × data — :func:`repro.distributed.collectives.ring_plan`; a flat
ring on O3, pod-major on O4 so consecutive hops stay on fast ICI).  Each
hop rotates the K/V panels one neighbour around the ring (``ppermute``)
while every device folds the visiting panel into its flash (m, l, acc)
online-softmax state — the cross-device generalisation of the kernel's own
K-panel recurrence.  Per-hop compute is a *per-shard registry dispatch* of
``flash_attention_state`` (pallas on TPU, interpret/xla elsewhere): the
chip kernel, one shard at a time, exactly like ``mesh_spmv``/``mesh_psum``.

Causal masking is **zig-zag balanced**: with contiguous sequence blocks,
rank 0's rows see one K panel and rank R-1's see all R — a R/2× load skew.
:func:`zigzag_perm` instead deals each rank the half-blocks ``(s, 2R-1-s)``
so every rank owns one early and one late slice; each hop then does the
same amount of unmasked work on every device.  Per hop the visiting panel
classifies *statically per half-block pair* into full / diagonal-causal /
masked, so the per-shard kernel only ever sees aligned causal or unmasked
calls:

    hop 0 (own panel)    q_lo×k_lo causal, q_hi×k_lo full, q_hi×k_hi causal
    source ring-before   both q halves × k_lo full (k_hi entirely masked)
    source ring-after    q_hi × whole panel full (q_lo entirely masked)

The variant registers as ``flash_attention``/``ring`` with ``scope='mesh'``
and degrades to the chip kernel exactly like ``mesh_psum``/``mesh_spmm``:
no ambient mesh, a 1-wide ring, or an L the ring doesn't divide all fall
back with identical outputs, and explicit ``variant=`` still pins.

Banded per-shard layouts (DESIGN.md §12): the hop-0 diagonal half-blocks
are the one place zig-zag still pays causal imbalance — a causal call
whose upper triangle is dead.  Those per-shard ``flash_attention_state``
dispatches now run the tile-skipping kernel's degenerate banded layout
(``kernels/flash_attention.py`` routes causal calls through compiled row
extents), so each diagonal half-block walks only its live K tiles instead
of launching the full grid and ``pl.when``-ing the upper triangle off —
striped attention at sub-block granularity, with no change here beyond
the dispatch.  Rich ``MaskSpec`` masks (windows / globals / block
patterns) stay chip-scoped: ``accepts`` rejects them, selection degrades
to the chip block-sparse kernel on replicated Q/K/V.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import registry
from repro.distributed.collectives import (RingPlan, ambient_ring_plan,
                                           ring_plan)

__all__ = ["ring_attention", "paged_ring_attention", "zigzag_perm"]


@functools.lru_cache(maxsize=None)
def zigzag_perm(length: int, ring: int):
    """(order, inverse) reordering the sequence so ring shard ``s`` holds
    the half-blocks ``(s, 2·ring-1-s)`` — one early and one late slice, so
    causal masking wastes the same panels on every rank.  ``x[..., order]``
    lays the sequence out for sharding; ``out[..., inverse]`` restores
    global order.  None when ``length`` doesn't split into 2·ring
    half-blocks (the contiguous layout is the only option then)."""
    if ring <= 1 or length % (2 * ring) != 0:
        return None
    h = length // (2 * ring)
    order = np.concatenate([
        np.r_[s * h:(s + 1) * h,
              (2 * ring - 1 - s) * h:(2 * ring - s) * h]
        for s in range(ring)])
    inv = np.argsort(order)
    return order, inv


# ---------------------------------------------------------------------------
# online-softmax state algebra (the merge the flash kernel does per K panel,
# lifted to whole per-hop states)
# ---------------------------------------------------------------------------

def _as_state(o, m, l):
    """(normalised o, m, l) -> the unnormalised (m, l, acc) carry."""
    return m, l, o.astype(jnp.float32) * l[..., None]


def _merge(carry, upd):
    m, l, acc = carry
    mu, lu, accu = upd
    m_new = jnp.maximum(m, mu)
    a = jnp.exp(m - m_new)
    b = jnp.exp(mu - m_new)
    return (m_new, l * a + lu * b,
            acc * a[..., None] + accu * b[..., None])


def _concat(lo, hi):
    """Concatenate two half-block states along the sequence axis."""
    return tuple(jnp.concatenate([a, b], axis=2) for a, b in zip(lo, hi))


def _split(st, half):
    return (tuple(x[:, :, :half] for x in st),
            tuple(x[:, :, half:] for x in st))


# ---------------------------------------------------------------------------
# the shard_map executable (one per plan × mask × ordering × plane × blocks)
# ---------------------------------------------------------------------------

def _state_fn(plane, blocks):
    """Per-shard flash dispatch: the chip formulation, one shard at a time
    (``variant=plane`` pins the resolved chip plane, like mesh_matmul)."""
    bq, bk = blocks

    def state(q, k, v, *, causal):
        o, m, l = registry.dispatch("flash_attention_state", q, k, v,
                                    causal=causal, block_q=bq, block_k=bk,
                                    variant=plane)
        return _as_state(o, m, l)
    return state


@functools.lru_cache(maxsize=None)
def _ring_exec(plan: RingPlan, causal: bool, zigzag: bool, plane: str,
               blocks):
    entry = plan.spec_entry()
    W = plan.size
    state = _state_fn(plane, blocks)

    def run(ql, kl, vl):
        half = ql.shape[2] // 2                     # static local half-block

        # -- hop 0: own K/V panel (the block classification is static) ----
        if not causal:
            st = state(ql, kl, vl, causal=False)
        elif not zigzag:
            st = state(ql, kl, vl, causal=True)
        else:
            q_lo, q_hi = ql[:, :, :half], ql[:, :, half:]
            k_lo, k_hi = kl[:, :, :half], kl[:, :, half:]
            v_lo, v_hi = vl[:, :, :half], vl[:, :, half:]
            st_lo = state(q_lo, k_lo, v_lo, causal=True)
            st_hi = _merge(state(q_hi, k_lo, v_lo, causal=False),
                           state(q_hi, k_hi, v_hi, causal=True))
            st = _concat(st_lo, st_hi)

        if W > 1:
            r = plan.ring_index()

            def body(carry, h):
                kl, vl, st = carry
                kl, vl = plan.shift(kl), plan.shift(vl)
                # the visiting panel started on rank j = (r - h) mod W
                if not causal:
                    st = _merge(st, state(ql, kl, vl, causal=False))
                elif not zigzag:
                    # contiguous: earlier blocks are fully visible, later
                    # blocks fully masked — h <= r <=> j < r
                    st = jax.lax.cond(
                        h <= r,
                        lambda st: _merge(st, state(ql, kl, vl,
                                                    causal=False)),
                        lambda st: st,
                        st)
                else:
                    def before(st):       # j < r: k_lo visible to all rows
                        return _merge(st, state(ql, kl[:, :, :half],
                                                vl[:, :, :half],
                                                causal=False))

                    def after(st):        # j > r: q_hi sees the whole panel
                        lo, hi = _split(st, half)
                        hi = _merge(hi, state(ql[:, :, half:], kl, vl,
                                              causal=False))
                        return _concat(lo, hi)

                    st = jax.lax.cond(h <= r, before, after, st)
                return (kl, vl, st), None

            (_, _, st), _ = jax.lax.scan(body, (kl, vl, st),
                                         jnp.arange(1, W))

        m, l, acc = st
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(ql.dtype)

    spec = P(None, None, entry, None)
    return jax.jit(jax.shard_map(run, mesh=plan.mesh,
                                 in_specs=(spec, spec, spec), out_specs=spec,
                                 check_vma=False))


@functools.lru_cache(maxsize=None)
def _ring_zigzag_exec(plan: RingPlan, plane: str, blocks, length: int):
    """Zig-zag wrapper: permute the sequence in, inverse-permute out — both
    gathers traced inside one jitted executable so XLA fuses them with the
    resharding (causal only; the unmasked form has nothing to balance)."""
    inner = _ring_exec(plan, True, True, plane, blocks)
    order, inv = zigzag_perm(length, plan.size)

    def run(q, k, v):
        return inner(q[:, :, order], k[:, :, order],
                     v[:, :, order])[:, :, inv]

    return jax.jit(run)


def ring_attention(q, k, v, *, causal: bool = True, mask=None, block_q=None,
                   block_k=None, order: Optional[str] = None):
    """Sequence-parallel attention over the ambient mesh's ring.

    ``order`` picks the sequence-block layout: 'zigzag' (default for
    causal — balanced masking) or 'contiguous' (default for full
    attention, where there is no mask to balance).  ``block_q``/``block_k``
    pin the per-shard kernel tiles, as on chip.  ``mask`` is honoured only
    when trivially dense (it lowers to the causal flag); richer specs are
    chip-scoped (see module docstring) and rejected here.
    """
    if mask is not None:
        if not mask.trivial_dense:
            raise ValueError(
                "ring attention only takes trivially-dense masks (plain "
                "causal); window/global/block specs run the chip "
                "block-sparse kernel")
        causal = mask.causal
    plan = ambient_ring_plan()
    if plan is None:
        raise RuntimeError(
            "ring attention invoked without an ambient O3/O4 mesh carrying "
            "a batch-role (pod/data) axis; enter use_level(O3) first")
    W = plan.size
    L = q.shape[2]
    if order is None:
        order = "zigzag" if causal else "contiguous"
    if order not in ("zigzag", "contiguous"):
        raise ValueError(f"unknown ring ordering {order!r}; choose "
                         "'zigzag' or 'contiguous'")
    zigzag = order == "zigzag" and causal      # full attention needs no balance
    need = 2 * W if zigzag else W
    if L % need != 0:
        raise ValueError(
            f"sequence length {L} does not split into {need} "
            f"{'half-' if zigzag else ''}blocks for a ring of {W}")
    plane = registry.resolve_backend()
    blocks = (block_q, block_k)
    if zigzag:
        return _ring_zigzag_exec(plan, plane, blocks, L)(q, k, v)
    return _ring_exec(plan, causal, False, plane, blocks)(q, k, v)


# ---------------------------------------------------------------------------
# registration: the mesh-scoped flash variant
# ---------------------------------------------------------------------------

def _ring_available(ctx: registry.SelectContext) -> bool:
    return (ctx.topology is not None and
            ring_plan(ctx.mesh, ctx.topology).size > 1)


def _ring_accepts(q, k, v, *, causal=True, mask=None, block_q=None,
                  block_k=None):
    """Self-attention panels whose length the ring divides: 2W half-blocks
    when causal (the zig-zag layout), W blocks when full.  Rich masks are
    chip-scoped (block-sparse kernel); trivially-dense ones lower to the
    causal flag."""
    if mask is not None:
        if not mask.trivial_dense:
            return False
        causal = mask.causal
    plan = ambient_ring_plan()
    if plan is None or plan.size <= 1:
        return False
    if getattr(q, "ndim", 0) != 4 or getattr(k, "ndim", 0) != 4:
        return False
    if q.shape[2] != k.shape[2] or q.shape[1] % k.shape[1] != 0:
        return False
    need = 2 * plan.size if causal else plan.size
    return q.shape[2] % need == 0


registry.register(
    "flash_attention", "ring", ring_attention, scope="mesh", cost=1.0,
    available=_ring_available, accepts=_ring_accepts,
    doc="sequence-parallel ring attention: Q/K/V shard L over pod x data, "
        "K/V panels rotate by ppermute, per-shard flash state merges "
        "across hops; zig-zag causal balancing")


# ---------------------------------------------------------------------------
# paged decode over the ring-sharded KV cache (DESIGN.md §13)
#
# Prefill rotates K/V panels around the ring (§10); decode inverts the
# movement: the paged pool stays pinned — page table position p is owned by
# ring shard p % W, shard r holding global page ids [r·P/W, (r+1)·P/W) —
# and only the one-token (o, m, l) partials travel, merged in a single
# pmax/psum step (the rotation schedule's reduction dual,
# RingPlan.psum/pmax).  Striped ownership keeps the pool balanced: a slot's
# pages deal out round-robin, so a long stream loads every shard equally
# instead of saturating one shard's range.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _paged_ring_exec(plan: RingPlan, plane: str):
    entry = plan.spec_entry()
    W = plan.size

    def run(q, kp, vp, table, lens):
        # q (B, H, 1, d) / table (B, n) / lens (B,) replicated;
        # kp/vp (P/W, hk, ps, d) — this shard's slice of the page pool
        r = plan.ring_index()
        p_local = kp.shape[0]
        b, n = table.shape
        ps = kp.shape[2]
        nloc = n // W

        # this shard's table positions (p % W == r), in ascending global
        # position order; trash-0 / foreign entries clip into range and are
        # masked off by llen below
        tl = table.reshape(b, nloc, W)
        mine = jax.lax.dynamic_index_in_dim(tl, r, axis=2, keepdims=False)
        local = jnp.clip(mine - r * p_local, 0, p_local - 1)   # (b, nloc)

        # valid tokens in this shard's view: global position j·W + r holds
        # tokens [pos·ps, pos·ps + ps); allocation fills positions in
        # order, so full pages precede the one partial page and the local
        # view is prefix-valid with length Σ fill_j
        pstart = (jnp.arange(nloc) * W + r) * ps               # (nloc,)
        fill = jnp.clip(lens[:, None] - pstart[None, :], 0, ps)
        llen = jnp.sum(fill, axis=1).astype(jnp.int32)         # (b,)

        kg = kp[local]                         # (b, nloc, hk, ps, d)
        vg = vp[local]
        hk, d = kp.shape[1], kp.shape[3]
        kg = kg.transpose(0, 2, 1, 3, 4).reshape(b, hk, nloc * ps, d)
        vg = vg.transpose(0, 2, 1, 3, 4).reshape(b, hk, nloc * ps, d)

        o, m, l = registry.dispatch("flash_attention_state", q, kg, vg,
                                    causal=False, kv_len=llen, variant=plane)
        # decode-side state merge: a shard with no live key carries
        # m == NEG_INF and its weight exp(m - mg) underflows to exactly 0
        mg = plan.pmax(m)
        w = jnp.exp(m - mg) * l
        lg = plan.psum(w)
        og = plan.psum(o.astype(jnp.float32) * w[..., None])
        out = og / jnp.maximum(lg, 1e-30)[..., None]
        return out.astype(q.dtype)

    rep = P(None, None, None, None)
    return jax.jit(jax.shard_map(
        run, mesh=plan.mesh,
        in_specs=(rep, P(entry, None, None, None),
                  P(entry, None, None, None), P(None, None), P(None)),
        out_specs=rep, check_vma=False))


def paged_ring_attention(q, kpages, vpages, table, lens):
    """Decode attention over the ring-sharded page pool: per-shard
    prefix-masked flash partials merged via the ring plan's pmax/psum dual.
    Numerically allclose (not bitwise) to the chip gather variant — the
    psum reassociates the (o·w, w) sums across shards."""
    plan = ambient_ring_plan()
    if plan is None:
        raise RuntimeError(
            "paged ring attention invoked without an ambient O3/O4 mesh "
            "carrying a batch-role (pod/data) axis; enter use_level(O3) "
            "first")
    plane = registry.resolve_backend()
    return _paged_ring_exec(plan, plane)(q, kpages, vpages, table, lens)


def _paged_ring_accepts(q, kpages, vpages, table, lens):
    plan = ambient_ring_plan()
    if plan is None or plan.size <= 1:
        return False
    W = plan.size
    return (kpages.shape[0] % W == 0 and table.shape[1] % W == 0
            and q.shape[1] % kpages.shape[1] == 0)


registry.register(
    "paged_attention", "ring", paged_ring_attention, scope="mesh", cost=1.0,
    available=_ring_available, accepts=_paged_ring_accepts,
    doc="decode over the ring-sharded page pool: striped page ownership, "
        "per-shard prefix-masked flash state, pmax/psum merge (the "
        "rotation schedule's reduction dual, DESIGN.md §13)")
