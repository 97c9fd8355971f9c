"""Pallas TPU kernel: flash attention with GQA (beyond-paper optimisation).

The paper's dense-linear-algebra dwarf (mod2am) dominates transformer
compute; its attention instance is the one place where the naive formulation
also *materialises* an O(L^2) intermediate.  This kernel applies the paper's
central lesson — restructure the recorded loop so the compiler can tile it —
in its strongest modern form: online-softmax tiling (Flash-Attention), K/V
panels streamed through VMEM with an f32 running (m, l, acc) state.

    grid = (batch, q_heads, Lq/bq, Lk/bk)        k panel innermost, sequential
    q tile   (bq, d)   VMEM        kv tiles (bk, d) VMEM
    scratch  m (bq,), l (bq,), acc (bq, d)  — f32, persists across k panels

GQA is folded into the BlockSpec index maps: the K/V index map sends q-head h
to kv-head h // (q_heads // kv_heads), so MQA (gemma-2b kv=1) and GQA
(qwen3 kv=8) reuse K/V panels across the q-head grid axis with no extra copies.

Causal masking is positional (iota compare) inside the kernel; fully-masked
panels are skipped via ``pl.when`` on the grid coordinates, halving work for
causal training shapes.

``return_state=True`` additionally emits the final online-softmax state —
the row maxima ``m`` and denominators ``l``, both (batch, q_heads, seq_q)
f32 — which is what the sequence-parallel ring variant (DESIGN.md §10)
needs to merge per-hop partial attention across K/V rotations: the
unnormalised accumulator is recovered as ``o * l`` and two states combine
exactly like two K panels inside this kernel.

Block-sparse tile skipping (DESIGN.md §12).  The dense grid above launches
every ``Lq/bq × Lk/bk`` step and masks dead ones — exactly the formulation
the paper's sparse kernel exists to avoid.  :func:`flash_attention_tiles`
instead takes a compiled :class:`~repro.sparse.maskcompiler.TileLayout`
and walks, per Q row, *only the live K tiles*: a recorded ``fori_loop``
over the row's ``rowp`` section with ``dynamic_slice`` K/V tile reads —
the BSR traversal shape of :func:`repro.kernels.spmm.spmm_bsr_kernel`,
with the SpMM accumulator replaced by the online-softmax (m, l, acc)
carry.  Tiles are classified statically by the compiler: the FULL loop
(``rowp[i]..mid[i]``) runs no masking at all; the PARTIAL edge loop
(``mid[i]..rowp[i+1]``) applies either one iota band compare (positional
specs — causal / sliding window) or a stored additive bias tile (global
tokens, arbitrary block patterns).  The plain-causal dense path routes
through the same machinery with the degenerate banded layout, so the
K grid is *bounded* per Q row by the compiled row extents instead of
launching every above-diagonal step and ``pl.when``-ing it off
(``row_extents=False`` keeps the legacy grid reachable for A/B parity).

The layout's index arrays (rowp/mid/prowp/cols) and the paged-decode
``kv_len`` ride in scalar memory (``pltpu.PrefetchScalarGridSpec``), where
loop bounds and index maps can read them; K/V sit whole per (batch,
kv-head) in VMEM and each live tile is read from the ref with ``pl.ds``.
The running (m, l) state is kept as (bq, 1) columns — the layout the row
reductions produce — and leaves the kernel as (batch, heads, seq_q, 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_kernel", "flash_attention_state_kernel",
           "flash_attention_lens_kernel", "flash_attention_lens_state_kernel",
           "flash_attention_tiles_kernel", "flash_attention_tiles_state_kernel",
           "flash_attention", "flash_attention_tiles", "merge_states",
           "NEG_INF"]

#: The additive mask value (finite, so exp() underflows to 0 instead of
#: producing inf - inf = nan) — shared by every attention formulation:
#: this kernel, the XLA oracles (kernels/ref.py), and the KV-cache decode
#: path (models/attention.py) all import it rather than inlining -1e30.
NEG_INF = -1e30


def merge_states(a, b):
    """Merge two online-softmax states ``(o, m, l)`` over the same queries.

    This is the kernel's K-panel recurrence lifted to whole states: two
    attention calls over disjoint key sets combine exactly like two K panels
    inside :func:`_fa_step`.  The distributed ring merge
    (``repro.distributed.attention._merge``) and the chunked-prefill merge
    (``chunk_attention`` in kernels/ops.py, DESIGN.md §13) are both this
    function; a state whose keys were all masked carries ``m == NEG_INF``
    and its weight ``exp(NEG_INF - m)`` underflows to exactly 0, so it
    drops out of the merge.
    """
    o_a, m_a, l_a = a
    o_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    w_a = jnp.exp(m_a - m) * l_a
    w_b = jnp.exp(m_b - m) * l_b
    l = w_a + w_b
    o = (o_a.astype(jnp.float32) * w_a[..., None]
         + o_b.astype(jnp.float32) * w_b[..., None])
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.astype(o_a.dtype), m, l


def _fa_step(
    q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    kv_len=None,
):
    """One grid step of the online-softmax recurrence: init the (m, l, acc)
    scratch on the first K panel, then fold this panel in (shared by the
    plain and the state-returning kernels).

    ``kv_len`` (this batch row's scalar, read from scalar memory) is the
    paged-decode prefix mask (DESIGN.md §13): keys at ``kpos >= kv_len``
    are dead.  A row with *no* live key anywhere leaves ``m == NEG_INF`` —
    its (o, m, l) is garbage, but the ring/state merge weights it by
    ``exp(m - m_g)`` which underflows to exactly 0, so empty shards/slots
    cancel."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Skip panels strictly above the diagonal when causal.
    run = (not causal) or (ik * block_k <= (iq + 1) * block_q - 1)

    @pl.when(run)
    def _panel():
        q = q_ref[0, 0]                                   # (bq, d)
        k = k_ref[0, 0]                                   # (bk, d)
        v = v_ref[0, 0]                                   # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        if kv_len is not None:
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos < kv_len, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur


def _flush(o_ref, m_ref, l_ref, acc_ref, ms_ref=None, ls_ref=None):
    """Normalise the accumulator into ``o`` (and emit (m, l) when asked)."""
    o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                   ).astype(o_ref.dtype)
    if ms_ref is not None:
        ms_ref[0, 0] = m_ref[...]
        ls_ref[0, 0] = l_ref[...]


def flash_attention_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, kv_steps: int, block_q: int, block_k: int,
):
    _fa_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale=scale,
             causal=causal, block_q=block_q, block_k=block_k)

    @pl.when(pl.program_id(3) == kv_steps - 1)
    def _():
        _flush(o_ref, m_ref, l_ref, acc_ref)


def flash_attention_state_kernel(
    q_ref, k_ref, v_ref, o_ref, ms_ref, ls_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, kv_steps: int, block_q: int, block_k: int,
):
    """Same recurrence; the flush also emits the final (m, l) state."""
    _fa_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale=scale,
             causal=causal, block_q=block_q, block_k=block_k)

    @pl.when(pl.program_id(3) == kv_steps - 1)
    def _():
        _flush(o_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref)


def flash_attention_lens_kernel(
    lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, kv_steps: int, block_q: int, block_k: int,
):
    """Dense-grid kernel with a per-batch key-prefix mask: only keys at
    positions ``< lens_ref[b]`` are live (``lens_ref`` is scalar-prefetched).
    This is the paged decode / chunked-prefill read path (DESIGN.md §13),
    where the K/V operand is a gathered page view whose valid length varies
    per slot."""
    _fa_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale=scale,
             causal=causal, block_q=block_q, block_k=block_k,
             kv_len=lens_ref[pl.program_id(0)])

    @pl.when(pl.program_id(3) == kv_steps - 1)
    def _():
        _flush(o_ref, m_ref, l_ref, acc_ref)


def flash_attention_lens_state_kernel(
    lens_ref, q_ref, k_ref, v_ref, o_ref, ms_ref, ls_ref, m_ref, l_ref,
    acc_ref,
    *, scale: float, causal: bool, kv_steps: int, block_q: int, block_k: int,
):
    """Prefix-masked recurrence; the flush also emits the final (m, l)."""
    _fa_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale=scale,
             causal=causal, block_q=block_q, block_k=block_k,
             kv_len=lens_ref[pl.program_id(0)])

    @pl.when(pl.program_id(3) == kv_steps - 1)
    def _():
        _flush(o_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref)


def _fa_tiles_scan(
    iq, q, k_ref, v_ref, rowp_ref, mid_ref, prowp_ref, cols_ref, bias_ref,
    *, scale, band, block_q: int, block_k: int,
):
    """Walk one Q row's live K tiles — the FULL loop (no masking), then the
    PARTIAL edge loop — and return the row's final (m, l, acc) carry.

    This is ``spmm_bsr_kernel``'s recorded _for over a ``rowp`` section with
    the accumulator swapped for the online-softmax recurrence of
    :func:`_fa_step`; ``band`` is the compiled ``(causal, window, offset)``
    of positional specs (edge tiles masked by one iota compare) or None
    (edge tiles add their stored bias tile).  Each live K/V tile is read
    straight from the (batch, kv-head) ref at ``pl.ds(c * block_k)``."""
    d = q.shape[-1]
    start = rowp_ref[iq]
    midp = mid_ref[iq]
    stop = rowp_ref[iq + 1]

    def fold(p, carry, *, masked: bool):
        m_prev, l_prev, acc = carry
        c = cols_ref[p]
        kv_rows = pl.ds(pl.multiple_of(c * block_k, block_k), block_k)
        kb = k_ref[0, 0, kv_rows, :]
        vb = v_ref[0, 0, kv_rows, :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            if band is not None:
                causal, window, off = band
                qpos = (iq * block_q + off) + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                kpos = c * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                if causal:
                    s = jnp.where(qpos >= kpos, s, NEG_INF)
                if window is not None:
                    live = ((qpos - kpos) < window) if causal else (
                        jnp.abs(qpos - kpos) < window)
                    s = jnp.where(live, s, NEG_INF)
            else:
                pidx = prowp_ref[iq] + (p - midp)
                s = s + bias_ref[pidx]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        pmat = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(pmat, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            pmat.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        return m_cur, l_cur, acc

    carry = (jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32),
             jnp.zeros((block_q, d), jnp.float32))
    carry = jax.lax.fori_loop(
        start, midp, functools.partial(fold, masked=False), carry)
    return jax.lax.fori_loop(
        midp, stop, functools.partial(fold, masked=True), carry)


def flash_attention_tiles_kernel(
    rowp_ref, mid_ref, prowp_ref, cols_ref, bias_ref,
    q_ref, k_ref, v_ref, o_ref,
    *, scale: float, band, block_q: int, block_k: int,
):
    """One Q row per grid step; K grid replaced by the row's live-tile span.
    Fully-dead rows (start == stop) fall through with l = 0 → output 0."""
    m, l, acc = _fa_tiles_scan(
        pl.program_id(2), q_ref[0, 0], k_ref, v_ref,
        rowp_ref, mid_ref, prowp_ref, cols_ref, bias_ref,
        scale=scale, band=band, block_q=block_q, block_k=block_k)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_tiles_state_kernel(
    rowp_ref, mid_ref, prowp_ref, cols_ref, bias_ref,
    q_ref, k_ref, v_ref, o_ref, ms_ref, ls_ref,
    *, scale: float, band, block_q: int, block_k: int,
):
    """Same walk; the flush also emits the (m, l) state for ring merging."""
    m, l, acc = _fa_tiles_scan(
        pl.program_id(2), q_ref[0, 0], k_ref, v_ref,
        rowp_ref, mid_ref, prowp_ref, cols_ref, bias_ref,
        scale=scale, band=band, block_q=block_q, block_k=block_k)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    ms_ref[0, 0] = m
    ls_ref[0, 0] = l


def _state_outputs(q, block_q, index_map):
    """Output shapes/specs of ``o`` plus the (m, l) state.  The state leaves
    the kernel as (batch, heads, seq_q, 1) columns; callers see
    (batch, heads, seq_q) after :func:`_squeeze_state`."""
    batch, q_heads, seq_q, d = q.shape
    o_spec = pl.BlockSpec((1, 1, block_q, d), index_map)
    state_spec = pl.BlockSpec((1, 1, block_q, 1), index_map)
    state_shape = jax.ShapeDtypeStruct((batch, q_heads, seq_q, 1),
                                       jnp.float32)
    return ((jax.ShapeDtypeStruct(q.shape, q.dtype), state_shape,
             state_shape), (o_spec, state_spec, state_spec))


def _squeeze_state(out):
    o, m, l = out
    return o, m[..., 0], l[..., 0]


def flash_attention_tiles(
    q: jax.Array,          # (batch, q_heads, seq_q, d)
    k: jax.Array,          # (batch, kv_heads, seq_k, d)
    v: jax.Array,          # (batch, kv_heads, seq_k, d)
    layout,                # repro.sparse.maskcompiler.TileLayout
    *,
    scale: float | None = None,
    return_state: bool = False,
    interpret: bool = False,
):
    """Tile-skipping flash attention over a compiled mask layout.

    The grid is (batch, q_heads, Lq/bq) — no K axis: each step walks only
    its row's live tiles, full-first (see module docstring).  K-tile order
    inside a row is ascending, so the plain-causal layout accumulates in
    exactly the dense kernel's panel order (bitwise-equal f32 outputs)."""
    batch, q_heads, seq_q, d = q.shape
    _, kv_heads, seq_k, _ = k.shape
    assert q_heads % kv_heads == 0
    group = q_heads // kv_heads
    bq, bk = layout.block_q, layout.block_k
    assert layout.shape == (seq_q, seq_k), (layout.shape, seq_q, seq_k)
    scale = scale if scale is not None else d ** -0.5
    nq = seq_q // bq

    if layout.ntiles == 0:          # every tile dead: attend to nothing
        o = jnp.zeros_like(q)
        if return_state:
            state = (jnp.full((batch, q_heads, seq_q), NEG_INF, jnp.float32),
                     jnp.zeros((batch, q_heads, seq_q), jnp.float32))
            return (o,) + state
        return o

    kernel = functools.partial(
        flash_attention_tiles_state_kernel if return_state
        else flash_attention_tiles_kernel,
        scale=scale, band=layout.band, block_q=bq, block_k=bk)

    def q_map(b, h, iq, *_):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, *_):
        return (b, h // group, 0, 0)

    if return_state:
        out_shape, out_specs = _state_outputs(q, bq, q_map)
    else:
        out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
        out_specs = pl.BlockSpec((1, 1, bq, d), q_map)
    npart = layout.biases.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(batch, q_heads, nq),
        in_specs=[
            pl.BlockSpec((npart, bq, bk), lambda b, h, iq, *_: (0, 0, 0)),
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, seq_k, d), kv_map),
            pl.BlockSpec((1, 1, seq_k, d), kv_map),
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        name="flash_attention_tiles",
        interpret=interpret,
    )(jnp.asarray(layout.rowp), jnp.asarray(layout.mid),
      jnp.asarray(layout.prowp), jnp.asarray(layout.cols),
      jnp.asarray(layout.biases), q, k, v)
    return _squeeze_state(out) if return_state else out


def flash_attention(
    q: jax.Array,          # (batch, q_heads, seq_q, d)
    k: jax.Array,          # (batch, kv_heads, seq_k, d)
    v: jax.Array,          # (batch, kv_heads, seq_k, d)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    return_state: bool = False,
    row_extents: bool = True,
    kv_len: jax.Array | None = None,
    interpret: bool = False,
):
    """Flash attention; with ``return_state`` returns ``(o, m, l)`` where
    ``o`` is the normalised output and ``m`` / ``l`` the per-row softmax
    max / denominator (batch, q_heads, seq_q) f32.

    Causal calls route through :func:`flash_attention_tiles` with the
    degenerate banded layout: the K grid is bounded per Q row by compiled
    row extents instead of launching every above-diagonal panel and
    ``pl.when``-ing it off.  ``row_extents=False`` restores the legacy
    full-grid kernel (the A/B baseline for the parity benchmark).

    ``kv_len`` — optional (batch,) int32 per-batch valid key prefix: keys
    at positions ``>= kv_len[b]`` are masked dead.  The paged serve tier
    (DESIGN.md §13) attends over gathered page views padded to the pool
    capacity; without the mask the zero-padding keys would contribute
    ``exp(0 - m)`` terms to the denominator.  Composes with ``causal``
    (prefix AND band); routes through the dense grid, not the tiles path."""
    batch, q_heads, seq_q, d = q.shape
    _, kv_heads, seq_k, _ = k.shape
    assert q_heads % kv_heads == 0
    group = q_heads // kv_heads
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0
    scale = scale if scale is not None else d ** -0.5

    if causal and row_extents and kv_len is None:
        from repro.sparse.maskcompiler import causal_layout
        return flash_attention_tiles(
            q, k, v, causal_layout(seq_q, seq_k, block_q, block_k),
            scale=scale, return_state=return_state, interpret=interpret)

    grid = (batch, q_heads, seq_q // block_q, seq_k // block_k)
    base = {
        (False, False): flash_attention_kernel,
        (False, True): flash_attention_state_kernel,
        (True, False): flash_attention_lens_kernel,
        (True, True): flash_attention_lens_state_kernel,
    }[(kv_len is not None, return_state)]
    kernel = functools.partial(base, scale=scale, causal=causal,
                               kv_steps=grid[3], block_q=block_q,
                               block_k=block_k)

    # index maps take trailing scalar-prefetch refs (the lens) when present
    def q_map(b, h, iq, ik, *_):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ik, *_):
        return (b, h // group, ik, 0)

    if return_state:
        out_shape, out_specs = _state_outputs(q, block_q, q_map)
    else:
        out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
        out_specs = pl.BlockSpec((1, 1, block_q, d), q_map)
    prefetch = () if kv_len is None else (kv_len.astype(jnp.int32),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        name="flash_attention",
        interpret=interpret,
    )(*prefetch, q, k, v)
    return _squeeze_state(out) if return_state else out
