"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematically transparent formulation; kernel tests
sweep shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import NEG_INF

__all__ = ["matmul_ref", "spmv_ell_ref", "spmv_dia_ref", "spmm_ell_ref",
           "spmm_bsr_ref", "bsr_todense_ref", "spgemm_bsr_ref",
           "fft_stage_ref", "fft_ref", "attention_ref",
           "attention_state_ref", "attention_masked_ref", "attention_chunked"]


def matmul_ref(a: jax.Array, b: jax.Array, out_dtype=None) -> jax.Array:
    out_dtype = out_dtype or a.dtype
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)).astype(out_dtype)


def spmv_ell_ref(values: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    return jnp.sum(values * x[cols], axis=1)


def spmv_dia_ref(diags: jax.Array, offsets: tuple[int, ...],
                 x: jax.Array, halo=None, dot: bool = False):
    """``y[i] = sum_d diags[d, i] * x[i + offsets[d]]``; past the ends of x
    the terms are 0, or with ``halo = (lo, hi)`` the max|offset| rows of x
    before and after it.  ``dot=True`` returns ``(y, sum(x * y))``."""
    n = diags.shape[1]
    m = max((abs(o) for o in offsets), default=0)
    lo, hi = halo if halo is not None else (jnp.zeros(m, x.dtype),) * 2
    xp = jnp.concatenate([lo, x, hi])
    y = jnp.zeros(n, diags.dtype)
    for d, off in enumerate(offsets):
        y = y + diags[d] * xp[m + off:m + off + n]
    return (y, jnp.sum(x * y)) if dot else y


def spmm_ell_ref(values: jax.Array, cols: jax.Array, x: jax.Array
                 ) -> jax.Array:
    """ELL × dense panel: y[i, :] = sum_w values[i, w] * x[cols[i, w], :]."""
    return jnp.einsum("iw,iwk->ik", values, x[cols])


def spmm_bsr_ref(values: jax.Array, cols: jax.Array, rowp: jax.Array,
                 x: jax.Array) -> jax.Array:
    """BSR × dense panel via per-block dense products + block-row
    segment-sum (the mathematically transparent formulation)."""
    from repro.numerics.sparse import csr_row_ids

    nblocks, bs, _ = values.shape
    n, k = x.shape
    nbrows = rowp.shape[0] - 1
    if nblocks == 0:
        return jnp.zeros((nbrows * bs, k), values.dtype)
    xb = x.reshape(n // bs, bs, k)
    prod = jnp.einsum("pij,pjk->pik", values, xb[cols])     # (nblocks, bs, k)
    seg = csr_row_ids(rowp, nblocks)
    out = jax.ops.segment_sum(prod, seg, num_segments=nbrows)
    return out.reshape(nbrows * bs, k)


def bsr_todense_ref(values: jax.Array, cols: jax.Array, rowp: jax.Array,
                    shape: tuple[int, int]) -> jax.Array:
    """BSR → dense, scatter-add over the block grid (jnp; device-side dual
    of the container's host ``todense``)."""
    from repro.numerics.sparse import csr_row_ids

    n, m = shape
    nblocks, bs, _ = values.shape
    nbr, nbc = n // bs, m // bs
    if nblocks == 0:
        return jnp.zeros((n, m), values.dtype)
    rows = csr_row_ids(rowp, nblocks)
    grid = jnp.zeros((nbr, nbc, bs, bs), values.dtype).at[rows, cols] \
        .add(values)
    return grid.transpose(0, 2, 1, 3).reshape(n, m)


def spgemm_bsr_ref(a_values, a_cols, a_rowp, b_values, b_cols, b_rowp,
                   a_shape: tuple[int, int], b_shape: tuple[int, int]
                   ) -> jax.Array:
    """SpGEMM dense oracle: densify both BSR operands and multiply (f32) —
    the always-correct, never-fast baseline of the two-phase kernel
    (DESIGN.md §15).  Returns the *dense* (n, m) product; the sparse test
    layer compares the kernel's pattern-gathered blocks against it."""
    ad = bsr_todense_ref(a_values, a_cols, a_rowp, a_shape)
    bd = bsr_todense_ref(b_values, b_cols, b_rowp, b_shape)
    return jnp.dot(ad.astype(jnp.float32),
                   bd.astype(jnp.float32)).astype(a_values.dtype)


def fft_stage_ref(data_re, data_im, tw_re, tw_im):
    """(n/2, 2) re/im -> (2, n/2) re/im: up row 0, down row 1."""
    er, orr = data_re[:, 0], data_re[:, 1]
    ei, oi = data_im[:, 0], data_im[:, 1]
    up_re, up_im = er + orr, ei + oi
    dr, di = er - orr, ei - oi
    down_re = dr * tw_re - di * tw_im
    down_im = dr * tw_im + di * tw_re
    return (jnp.stack([up_re, down_re]), jnp.stack([up_im, down_im]))


def fft_ref(x: jax.Array) -> jax.Array:
    return jnp.fft.fft(x)


def attention_ref(q, k, v, *, causal: bool = True, scale=None) -> jax.Array:
    """(b, hq, lq, d) x (b, hk, lk, d) GQA attention, f32 softmax."""
    return attention_state_ref(q, k, v, causal=causal, scale=scale)[0]


def attention_state_ref(q, k, v, *, causal: bool = True, scale=None,
                        kv_len=None
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`attention_ref` that also returns the online-softmax state —
    ``(o, m, l)`` with row maxima ``m`` and denominators ``l`` both
    (b, hq, lq) f32 — the per-hop contract of the sequence-parallel ring
    variant (mirrors the flash kernel's ``return_state=True``).

    ``kv_len`` — optional (b,) int32 valid key prefix (the paged serve
    tier's gathered-page mask, DESIGN.md §13): keys at positions
    ``>= kv_len[b]`` are dead.  A batch row with no live key keeps
    ``m == NEG_INF`` and ``l == lk`` (exp(0) per dead entry) — garbage by
    construction, cancelled in any state merge by its ``exp(m - m_g) == 0``
    weight, exactly like the flash kernel's prefix-masked path."""
    b, hq, lq, d = q.shape
    _, hk, lk, _ = k.shape
    group = hq // hk
    kk = jnp.repeat(k, group, axis=1) if group > 1 else k
    vv = jnp.repeat(v, group, axis=1) if group > 1 else v
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        s = jnp.where(mask, s, NEG_INF)
    if kv_len is not None:
        live = jnp.arange(lk)[None, None, None, :] < kv_len[:, None, None, None]
        s = jnp.where(live, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    out = out / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype), m, l


def attention_masked_ref(q, k, v, mask, *, scale=None) -> jax.Array:
    """GQA attention under an arbitrary bool mask (lq, lk), True = attend —
    the oracle of the block-sparse tile-skipping kernel (DESIGN.md §12).

    Fully-masked rows output exactly 0, matching the kernel (which never
    launches their tiles, leaving l = 0)."""
    b, hq, lq, d = q.shape
    _, hk, lk, _ = k.shape
    group = hq // hk
    kk = jnp.repeat(k, group, axis=1) if group > 1 else k
    vv = jnp.repeat(v, group, axis=1) if group > 1 else v
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    # dead rows: m == s == NEG_INF gives exp(0) = 1 per entry; zero them so
    # the row sums to l = 0 and the output is 0, like the skipped tiles
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return (out / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def attention_chunked(q, k, v, *, causal: bool = True, scale=None,
                      block_kv: int = 1024) -> jax.Array:
    """Streaming-softmax attention: lax.scan over KV blocks with a running
    (max, denom, acc) carry — the flash-attention schedule expressed at the
    XLA level (§Perf iteration 2).

    HBM traffic is O(Lq·block_kv) per step instead of the O(Lq·Lk) score
    materialisation of :func:`attention_ref`; the per-block body is
    rematerialised in the backward pass, so residuals stay O(Lq·D) per
    block.  Exact same math as the oracle (tested allclose).
    """
    b, hq, lq, d = q.shape
    _, hk, lk, _ = k.shape
    group = hq // hk
    kk = jnp.repeat(k, group, axis=1) if group > 1 else k
    vv = jnp.repeat(v, group, axis=1) if group > 1 else v
    scale = scale if scale is not None else d ** -0.5
    assert lk % block_kv == 0, (lk, block_kv)
    nb = lk // block_kv

    q32 = q.astype(jnp.float32) * scale
    kb = kk.reshape(b, hq, nb, block_kv, d).transpose(2, 0, 1, 3, 4)
    vb = vv.reshape(b, hq, nb, block_kv, d).transpose(2, 0, 1, 3, 4)
    starts = jnp.arange(nb) * block_kv
    qi = jnp.arange(lq)[:, None] + (lk - lq)      # kv offset (prefill: 0)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, j0 = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kblk.astype(jnp.float32))
        if causal:
            kj = j0 + jnp.arange(block_kv)[None, :]
            s = jnp.where(qi >= kj, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, hq, lq), -jnp.inf, jnp.float32),
            jnp.zeros((b, hq, lq), jnp.float32),
            jnp.zeros((b, hq, lq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), init,
                                  (kb, vb, starts))
    return (acc / l[..., None]).astype(q.dtype)
