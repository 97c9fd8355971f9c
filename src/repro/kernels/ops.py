"""jit'd public entry points for the Pallas kernels.

All variant selection flows through :mod:`repro.core.registry` — this module
only *registers* one variant per retargeting plane for each op and keeps the
thin public wrappers.  The planes (``repro.core.registry.PLANES``):

    'pallas'     pl.pallas_call compiled for TPU (production)
    'interpret'  pl.pallas_call(interpret=True) — kernel body executed on CPU,
                 used by the test suite to validate kernels in this container
    'xla'        the pure-jnp reference path (repro.kernels.ref) — what the
                 multi-pod dry-run lowers, so cost_analysis reflects the XLA
                 collectives/fusions rather than opaque custom-calls

``backend(name)`` / the ``REPRO_KERNELS`` env var request a plane;
resolution (including the pallas-off-TPU -> xla fallback) is the registry's
job.  Default: 'pallas' on TPU, 'xla' elsewhere.

Pad-to-block/unpad is the :func:`repro.core.blocking.blocked` combinator;
block sizes come from the autotune cache (``results/autotune.json``) instead
of hardcoded 128s, with explicit per-call overrides still honoured.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.core.blocking import blocked, resolve_blocks
from repro.core.registry import (use_backend as backend,          # noqa: F401
                                 Cost,
                                 resolve_backend as current_backend)
from repro.kernels import fft as fft_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import matmul as mm_k
from repro.kernels import ref
from repro.kernels import spmv as spmv_k
from repro.numerics.fft import bitrev_permutation, split_stream_twiddles
from repro.sparse.maskcompiler import compile_layout, dense_mask
from repro.sparse.selector import BLOCKSPARSE_MAX_DENSITY

__all__ = ["backend", "current_backend", "matmul", "spmv_ell", "spmv_dia",
           "spmv_dia_dots",
           "fft", "flash_attention", "flash_attention_state",
           "paged_attention", "chunk_attention", "page_gather"]


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

def _matmul_inner(a, b, *, blocks, interpret):
    return mm_k.matmul(a, b, block_m=blocks["m"], block_n=blocks["n"],
                       block_k=blocks["k"], interpret=interpret)


_matmul_blocked = blocked(
    "matmul", _matmul_inner,
    pad={0: ("m", "k"), 1: ("k", "n")}, out=("m", "n"),
    defaults={"m": 128, "n": 128, "k": 128},
    candidates=({"m": 256, "n": 256}, {"m": 64, "n": 64, "k": 64},
                {"k": 256}, {"m": 256, "k": 64}),
)


def _mm_overrides(block_m, block_n, block_k):
    return {"m": block_m, "n": block_n, "k": block_k}


@registry.register("matmul", "pallas", plane="pallas", cost=Cost.PALLAS,
                   doc="blocked MXU kernel (kernels/matmul.py)")
def _matmul_pallas(a, b, *, block_m=None, block_n=None, block_k=None):
    return _matmul_blocked(a, b, interpret=False,
                           overrides=_mm_overrides(block_m, block_n, block_k))


@registry.register("matmul", "interpret", plane="interpret",
                   cost=Cost.INTERPRET,
                   doc="same kernel, interpret mode (CPU validation)")
def _matmul_interpret(a, b, *, block_m=None, block_n=None, block_k=None):
    return _matmul_blocked(a, b, interpret=True,
                           overrides=_mm_overrides(block_m, block_n, block_k))


_matmul_ref_jit = jax.jit(ref.matmul_ref)


@registry.register("matmul", "xla", plane="xla", cost=Cost.XLA,
                   doc="pure-jnp reference (XLA dot)")
def _matmul_xla(a, b, *, block_m=None, block_n=None, block_k=None):
    return _matmul_ref_jit(a, b)


def matmul(a, b, *, block_m=None, block_n=None, block_k=None):
    """Blocked matmul (pads to block multiples; f32 accumulation).

    Block sizes default to the autotuned/cached values; pass them explicitly
    to pin a configuration."""
    return registry.dispatch("matmul", a, b, block_m=block_m,
                             block_n=block_n, block_k=block_k)


# ---------------------------------------------------------------------------
# SpMV (ELL + DIA layouts)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def _spmv_ell_impl(values, cols, x, interpret):
    return spmv_k.spmv_ell(values, cols, x, interpret=interpret)


@registry.register("spmv_ell", "pallas", plane="pallas", cost=Cost.PALLAS,
                   doc="slot-major lane-gather ELL kernel (kernels/spmv.py)")
def _spmv_ell_pallas(values, cols, x):
    return _spmv_ell_impl(values, cols, x, interpret=False)


@registry.register("spmv_ell", "interpret", plane="interpret",
                   cost=Cost.INTERPRET)
def _spmv_ell_interpret(values, cols, x):
    return _spmv_ell_impl(values, cols, x, interpret=True)


_spmv_ell_ref_jit = jax.jit(ref.spmv_ell_ref)


@registry.register("spmv_ell", "xla", plane="xla", cost=Cost.XLA,
                   doc="gather + row-reduce reference")
def _spmv_ell_xla(values, cols, x):
    return _spmv_ell_ref_jit(values, cols, x)


def spmv_ell(values, cols, x):
    """ELL SpMV.  The ``pallas`` kernel's work grows with each slot's column
    spread (DESIGN.md §2): run large matrices whose columns have no
    locality under ``registry.use_backend("xla")``."""
    return registry.dispatch("spmv_ell", values, cols, x)


@functools.partial(jax.jit, static_argnames=("offsets", "dot", "interpret"))
def _spmv_dia_impl(diags, offsets, x, halo, dot, interpret):
    return spmv_k.spmv_dia(diags, offsets, x, halo=halo, dot=dot,
                           interpret=interpret)


@registry.register("spmv_dia", "pallas", plane="pallas", cost=Cost.PALLAS,
                   doc="banded shifted-FMA kernel, gather-free")
def _spmv_dia_pallas(diags, offsets, x, halo=None, dot=False):
    return _spmv_dia_impl(diags, offsets, x, halo, dot, interpret=False)


@registry.register("spmv_dia", "interpret", plane="interpret",
                   cost=Cost.INTERPRET)
def _spmv_dia_interpret(diags, offsets, x, halo=None, dot=False):
    return _spmv_dia_impl(diags, offsets, x, halo, dot, interpret=True)


_spmv_dia_ref_jit = jax.jit(ref.spmv_dia_ref,
                            static_argnames=("offsets", "dot"))


@registry.register("spmv_dia", "xla", plane="xla", cost=Cost.XLA)
def _spmv_dia_xla(diags, offsets, x, halo=None, dot=False):
    return _spmv_dia_ref_jit(diags, offsets, x, halo, dot=dot)


#: the list :func:`spmv_dia` appends x . y to, while one is open
_dia_dots: contextvars.ContextVar = contextvars.ContextVar("spmv_dia_dots",
                                                           default=None)


@contextlib.contextmanager
def spmv_dia_dots():
    """Inside it, each :func:`spmv_dia` call also takes ``x . y`` over its
    rows, which the Pallas kernel does as y streams out (``dot=True``), and
    appends it to the list this yields; y returns as always.  CG reads
    p.Ap so, from the call that makes Ap, and keeps reading Ap through
    ``spmv_dia`` alone, the op's one entry point."""
    dots = []
    token = _dia_dots.set(dots)
    try:
        yield dots
    finally:
        _dia_dots.reset(token)


def spmv_dia(diags, offsets, x, halo=None):
    """DIA SpMV, ``y[i] = sum_d diags[d, i] * x[i + offsets[d]]``.  ``halo
    = (lo, hi)`` gives the max|offset| rows of x before and after these
    rows (a row shard's neighbours'); None reads zeros past the ends.
    Inside :func:`spmv_dia_dots` it takes ``x . y`` too."""
    dots = _dia_dots.get()
    out = registry.dispatch("spmv_dia", diags, tuple(offsets), x, halo=halo,
                            dot=dots is not None)
    if dots is None:
        return out
    y, xy = out
    dots.append(xy)
    return y


# ---------------------------------------------------------------------------
# FFT (full transform = tangle + log2(n) fused stage kernels)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def _fft_stages(x, interpret):
    n = x.shape[0]
    rdtype = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
    perm = bitrev_permutation(n)
    tw = split_stream_twiddles(n)
    tw_re = jnp.asarray(tw.real, rdtype)
    tw_im = jnp.asarray(tw.imag, rdtype)
    data = x[perm]
    re, im = jnp.real(data).astype(rdtype), jnp.imag(data).astype(rdtype)
    m, i = n // 2, 1
    while i < n:
        stage_tw_re = jnp.tile(tw_re[:m], i)
        stage_tw_im = jnp.tile(tw_im[:m], i)
        re, im = fft_k.fft_stage(re.reshape(n // 2, 2).T,
                                 im.reshape(n // 2, 2).T, stage_tw_re,
                                 stage_tw_im, interpret=interpret)
        re, im = re.reshape(n), im.reshape(n)
        m >>= 1
        i <<= 1
    return (re + 1j * im).astype(x.dtype)


def _pow2(n: int) -> bool:
    return n >= 2 and (n & (n - 1)) == 0


def _fft_accepts(x):
    return _pow2(x.shape[0])


@registry.register("fft", "pallas", plane="pallas", cost=Cost.PALLAS,
                   accepts=_fft_accepts,
                   doc="split-stream butterfly stages (kernels/fft.py)")
def _fft_pallas(x):
    return _fft_stages(x, interpret=False)


@registry.register("fft", "interpret", plane="interpret", cost=Cost.INTERPRET,
                   accepts=_fft_accepts)
def _fft_interpret(x):
    return _fft_stages(x, interpret=True)


_fft_ref_jit = jax.jit(ref.fft_ref)


@registry.register("fft", "xla", plane="xla", cost=Cost.XLA,
                   doc="jnp.fft reference")
def _fft_xla(x):
    return _fft_ref_jit(x)


def fft(x):
    """1-D complex FFT, split-stream stages (power-of-two length)."""
    x = x.astype(jnp.complex64) if x.dtype != jnp.complex128 else x
    return registry.dispatch("fft", x)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

_FA_DEFAULTS = {"q": 128, "k": 128}
_FA_CANDIDATES = ({"q": 256}, {"k": 256}, {"q": 256, "k": 256},
                  {"q": 64, "k": 64})


def _fit_block(n: int, target: int) -> int:
    """The largest block <= target that divides n (the per-shard sequence
    slices the ring variant dispatches are arbitrary divisors of L, so the
    kernel's divisibility contract is met by shrinking the block)."""
    b = min(target, n)
    while n % b:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _fa_impl(q, k, v, causal, block_q, block_k, interpret):
    return fa_k.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                block_k=block_k, interpret=interpret)


def _fa_accepts(q, k, v, *, causal=True, mask=None, block_q=None,
                block_k=None):
    """The kernel needs grouped heads and block-divisible sequence lengths
    (blocks are clamped to the sequence, so short sequences always fit).
    Masks are taken only when trivially dense (plain causal / no mask —
    the kernel's native forms); richer specs go block-sparse or oracle."""
    if mask is not None and not mask.trivial_dense:
        return False
    lq, lk = q.shape[2], k.shape[2]
    bq = min(block_q or _FA_DEFAULTS["q"], lq)
    bk = min(block_k or _FA_DEFAULTS["k"], lk)
    return (q.shape[1] % k.shape[1] == 0 and lq % bq == 0 and lk % bk == 0)


def _fa_kernel_variant(interpret):
    def impl(q, k, v, *, causal=True, mask=None, block_q=None, block_k=None):
        if mask is not None:      # trivially dense: lower to the causal flag
            causal = mask.causal
        if block_q is not None and block_k is not None:   # fully pinned
            return _fa_impl(q, k, v, causal, block_q, block_k, interpret)
        dims = {"b": q.shape[0], "h": q.shape[1], "lq": q.shape[2],
                "lk": k.shape[2], "d": q.shape[3]}
        measure = None
        if not isinstance(q, jax.core.Tracer):
            def measure(bl):
                import time as _t
                out = _fa_impl(q, k, v, causal, bl["q"], bl["k"], interpret)
                jax.block_until_ready(out)
                t0 = _t.perf_counter()
                jax.block_until_ready(
                    _fa_impl(q, k, v, causal, bl["q"], bl["k"], interpret))
                return _t.perf_counter() - t0
        bl = resolve_blocks("flash_attention", dims, str(q.dtype),
                            _FA_DEFAULTS, _FA_CANDIDATES, measure)
        bq = block_q or bl["q"]
        bk = block_k or bl["k"]
        return _fa_impl(q, k, v, causal, bq, bk, interpret)
    return impl


registry.register("flash_attention", "pallas", _fa_kernel_variant(False),
                  plane="pallas", cost=Cost.PALLAS, accepts=_fa_accepts,
                  doc="online-softmax GQA kernel (kernels/flash_attention.py)")
registry.register("flash_attention", "interpret", _fa_kernel_variant(True),
                  plane="interpret", cost=Cost.INTERPRET, accepts=_fa_accepts)


# -- block-sparse: the tile-skipping kernel over a compiled mask layout ----

def _bs_blocks(lq, lk, block_q, block_k):
    return (_fit_block(lq, block_q or _FA_DEFAULTS["q"]),
            _fit_block(lk, block_k or _FA_DEFAULTS["k"]))


@functools.lru_cache(maxsize=None)
def _bs_exec(mask, lq, lk, bq, bk, interpret):
    """One jitted executable per (spec, shape, blocks, plane); the compiled
    TileLayout arrays ride along as constants, like the FFT twiddles."""
    layout = compile_layout(mask, lq, lk, bq, bk)

    def run(q, k, v):
        return fa_k.flash_attention_tiles(q, k, v, layout,
                                          interpret=interpret)
    return jax.jit(run)


def _bs_accepts(q, k, v, *, causal=True, mask=None, block_q=None,
                block_k=None):
    """Tile density drives the dense ↔ block-sparse crossover (DESIGN.md
    §12): masks a dense kernel expresses natively (plain causal) take the
    tile-skipping path only under ``BLOCKSPARSE_MAX_DENSITY``; masks it
    cannot (windows, globals, block patterns) always do — the oracle is
    the only other formulation that understands them."""
    if mask is None or q.shape[1] % k.shape[1] != 0:
        return False
    lq, lk = q.shape[2], k.shape[2]
    bq, bk = _bs_blocks(lq, lk, block_q, block_k)
    try:
        layout = compile_layout(mask, lq, lk, bq, bk)
    except ValueError:        # e.g. a block pattern that doesn't cover (lq, lk)
        return False
    if mask.trivial_dense:
        return layout.density <= BLOCKSPARSE_MAX_DENSITY
    return True


def _bs_kernel_variant(interpret):
    def impl(q, k, v, *, causal=True, mask=None, block_q=None, block_k=None):
        lq, lk = q.shape[2], k.shape[2]
        bq, bk = _bs_blocks(lq, lk, block_q, block_k)
        return _bs_exec(mask, lq, lk, bq, bk, interpret)(q, k, v)
    return impl


registry.register(
    "flash_attention", "blocksparse", _bs_kernel_variant(False),
    plane="pallas", cost=Cost.BLOCKSPARSE, accepts=_bs_accepts,
    doc="tile-skipping flash over a compiled mask layout: per-Q-row live "
        "tiles only, BSR traversal (kernels/flash_attention.py §tiles)")
registry.register(
    "flash_attention", "blocksparse_interpret", _bs_kernel_variant(True),
    plane="interpret", cost=Cost.INTERPRET, accepts=_bs_accepts)


_attn_ref_jit = jax.jit(ref.attention_ref, static_argnames=("causal",))
_attn_masked_ref_jit = jax.jit(ref.attention_masked_ref)


@functools.lru_cache(maxsize=None)
def _dense_mask_arr(mask, lq, lk):
    # host numpy, never a device array: caching a jnp constant created
    # under a jit trace would leak that trace's tracer into later callers
    return dense_mask(mask, lq, lk)


@registry.register("flash_attention", "xla", plane="xla", cost=Cost.XLA,
                   doc="materialising oracle (short sequences; any mask)")
def _attn_xla(q, k, v, *, causal=True, mask=None, block_q=None, block_k=None):
    if mask is not None:
        if mask.trivial_dense:
            return _attn_ref_jit(q, k, v, causal=mask.causal)
        return _attn_masked_ref_jit(q, k, v,
                                    _dense_mask_arr(mask, q.shape[2],
                                                    k.shape[2]))
    return _attn_ref_jit(q, k, v, causal=causal)


@functools.partial(jax.jit, static_argnames=("causal", "block_kv"))
def _attn_chunked_jit(q, k, v, causal, block_kv):
    return ref.attention_chunked(q, k, v, causal=causal, block_kv=block_kv)


def _chunked_accepts(q, k, v, *, causal=True, mask=None, block_q=None,
                     block_k=None):
    # long sequences: stream over KV blocks (flash schedule at the XLA
    # level) instead of materialising (B, H, Lq, Lk) scores — §Perf
    # iteration 2; short sequences keep the transparent oracle
    if mask is not None and not mask.trivial_dense:
        return False
    return k.shape[2] >= 4096 and k.shape[2] % 1024 == 0


@registry.register("flash_attention", "xla_chunked", plane="xla",
                   cost=Cost.XLA_CHUNKED,
                   accepts=_chunked_accepts,
                   doc="KV-streamed flash schedule at the XLA level")
def _attn_xla_chunked(q, k, v, *, causal=True, mask=None, block_q=None,
                      block_k=None):
    if mask is not None:      # trivially dense (accepts gates the rest)
        causal = mask.causal
    return _attn_chunked_jit(q, k, v, causal, 1024)


def flash_attention(q, k, v, *, causal=True, mask=None, block_q=None,
                    block_k=None):
    """Registry-dispatched attention.  ``mask`` is an optional
    :class:`repro.sparse.maskcompiler.MaskSpec`; when given it fully
    specifies the masking and ``causal`` is ignored (write
    ``MaskSpec(causal=True, window=w)``, not ``causal=True`` plus a window
    spec).  Density-gated selection picks the tile-skipping block-sparse
    kernel or the dense grid per call (DESIGN.md §12)."""
    return registry.dispatch("flash_attention", q, k, v, causal=causal,
                             mask=mask, block_q=block_q, block_k=block_k)


# ---------------------------------------------------------------------------
# flash attention with state: (o, m, l) — the per-hop contract of the
# sequence-parallel ring variant (repro.distributed.attention, DESIGN.md §10)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _fa_state_impl(q, k, v, kv_len, causal, block_q, block_k, interpret):
    return fa_k.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                block_k=block_k, return_state=True,
                                kv_len=kv_len, interpret=interpret)


def _fa_state_kernel_variant(interpret):
    def impl(q, k, v, *, causal=True, kv_len=None, block_q=None,
             block_k=None):
        bq = _fit_block(q.shape[2], block_q or _FA_DEFAULTS["q"])
        bk = _fit_block(k.shape[2], block_k or _FA_DEFAULTS["k"])
        return _fa_state_impl(q, k, v, kv_len, causal, bq, bk, interpret)
    return impl


def _fa_state_accepts(q, k, v, *, causal=True, kv_len=None, block_q=None,
                      block_k=None):
    return q.shape[1] % k.shape[1] == 0


registry.register("flash_attention_state", "pallas",
                  _fa_state_kernel_variant(False), plane="pallas",
                  cost=Cost.PALLAS,
                  accepts=_fa_state_accepts,
                  doc="GQA flash kernel emitting the (m, l) softmax state")
registry.register("flash_attention_state", "interpret",
                  _fa_state_kernel_variant(True), plane="interpret",
                  cost=Cost.INTERPRET, accepts=_fa_state_accepts)


_attn_state_ref_jit = jax.jit(ref.attention_state_ref,
                              static_argnames=("causal",))


@registry.register("flash_attention_state", "xla", plane="xla", cost=Cost.XLA,
                   accepts=_fa_state_accepts,
                   doc="materialising oracle returning (o, m, l)")
def _attn_state_xla(q, k, v, *, causal=True, kv_len=None, block_q=None,
                    block_k=None):
    return _attn_state_ref_jit(q, k, v, causal=causal, kv_len=kv_len)


def flash_attention_state(q, k, v, *, causal=True, kv_len=None, block_q=None,
                          block_k=None, variant=None):
    """Attention that also returns the online-softmax (m, l) row state —
    what the ring variant merges across K/V rotations.

    ``kv_len`` — optional (batch,) int32 valid key prefix: keys at
    positions ``>= kv_len[b]`` are masked dead (the serve tier's
    gathered-page views are padded to pool capacity, DESIGN.md §13)."""
    return registry.dispatch("flash_attention_state", q, k, v,
                             variant=variant, causal=causal, kv_len=kv_len,
                             block_q=block_q, block_k=block_k)


# ---------------------------------------------------------------------------
# paged attention: one-token decode over the serve tier's paged KV cache
# (DESIGN.md §13).  The chip variant gathers the slot's pages into a dense
# per-slot view and prefix-masks the unfilled tail; the mesh variant
# (repro.distributed.attention) computes per-shard (o, m, l) partials over
# ring-sharded pages and merges them with the ring plan's psum dual.
# ---------------------------------------------------------------------------


def page_gather(pages, table):
    """Gather a paged pool into dense per-slot K/V views.

    ``pages`` (P, kv_heads, page_size, d) + ``table`` (B, n) of global page
    ids -> (B, kv_heads, n * page_size, d) in table-position order.  Unused
    table entries point at the reserved trash page 0; the caller masks them
    off via ``kv_len`` (allocation fills positions in order, so the valid
    region is a prefix)."""
    b, n = table.shape
    _, kv_heads, ps, d = pages.shape
    g = pages[table]                                 # (B, n, hk, ps, d)
    return g.transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, n * ps, d)


@functools.partial(jax.jit, static_argnames=("plane",))
def _paged_gather_jit(q, kpages, vpages, table, lens, *, plane):
    kg = page_gather(kpages, table)
    vg = page_gather(vpages, table)
    o, _, _ = flash_attention_state(q, kg, vg, causal=False, kv_len=lens,
                                    variant=plane)
    return o


def _paged_gather_impl(q, kpages, vpages, table, lens):
    # pin the inner state dispatch to the resolved plane *outside* the jit
    # trace (same pattern as the ring variant) so a later use_backend()
    # switch is not shadowed by a stale shape-keyed executable
    return _paged_gather_jit(q, kpages, vpages, table, lens,
                             plane=registry.resolve_backend())


def _paged_accepts(q, kpages, vpages, table, lens):
    return q.shape[1] % kpages.shape[1] == 0


registry.register(
    "paged_attention", "gather", _paged_gather_impl,
    plane=None, cost=Cost.XLA, accepts=_paged_accepts,
    doc="chip decode: gather the slot's pages into a dense view, "
        "prefix-masked flash over it (DESIGN.md §13)")


def paged_attention(q, kpages, vpages, table, lens, *, variant=None):
    """Decode attention over a paged KV cache: ``q`` (B, H, 1, d) against
    the pages owned by each slot's ``table`` row, with ``lens`` (B,) valid
    token counts.  Mesh-scoped under an ambient ring mesh (per-shard state
    partials + psum merge); chip-scoped otherwise."""
    return registry.dispatch("paged_attention", q, kpages, vpages, table,
                             lens, variant=variant)


# ---------------------------------------------------------------------------
# chunk attention: one prefill chunk against (gathered prefix + itself)
# — the chunked-prefill read path (DESIGN.md §13)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("plane",))
def _chunk_merge_jit(q, kp, vp, plen, kc, vc, *, plane):
    prefix = flash_attention_state(q, kp, vp, causal=False, kv_len=plen,
                                   variant=plane)
    chunk = flash_attention_state(q, kc, vc, causal=True, variant=plane)
    return fa_k.merge_states(prefix, chunk)[0]


def _chunk_merge_impl(q, kp, vp, plen, kc, vc):
    return _chunk_merge_jit(q, kp, vp, plen, kc, vc,
                            plane=registry.resolve_backend())


@jax.jit
def _chunk_oracle_impl(q, kp, vp, plen, kc, vc):
    """Contiguous-layout oracle: gathers ``[prefix[:plen] || chunk]`` into a
    fixed-capacity buffer so every valid key occupies the same index it has
    in a one-shot prefill over the same tokens — softmax reductions then
    fold the identical nonzero terms in the identical order, which is what
    makes chunked prefill *bitwise* equal to one-shot on f32 (the merge
    variant is allclose-exact but reassociates the denominator)."""
    b, hq, c, d = q.shape
    _, hk, cap, _ = kp.shape
    group = hq // hk
    cat_k = jnp.concatenate([kp, kc], axis=2)        # (b, hk, cap + c, d)
    cat_v = jnp.concatenate([vp, vc], axis=2)
    j = jnp.arange(cap)
    # index map: buffer position j < plen reads the prefix, positions
    # [plen, plen + c) read the chunk, the dead tail clamps (masked below)
    src = jnp.where(j[None, :] < plen[:, None], j[None, :],
                    jnp.clip(cap + j[None, :] - plen[:, None], 0,
                             cap + c - 1))
    idx = src[:, None, :, None]
    kcat = jnp.take_along_axis(cat_k, idx, axis=2)   # (b, hk, cap, d)
    vcat = jnp.take_along_axis(cat_v, idx, axis=2)
    kk = jnp.repeat(kcat, group, axis=1) if group > 1 else kcat
    vv = jnp.repeat(vcat, group, axis=1) if group > 1 else vcat
    scale = d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    qpos = plen[:, None, None, None] + jnp.arange(c)[None, None, :, None]
    kpos = j[None, None, None, :]
    live = kpos <= qpos                              # causal at offset plen
    s = jnp.where(live, s, fa_k.NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(live, p, 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    out = out / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _chunk_accepts(q, kp, vp, plen, kc, vc):
    return (q.shape[1] % kp.shape[1] == 0
            and q.shape[2] == kc.shape[2])


registry.register(
    "chunk_attention", "merge", _chunk_merge_impl,
    plane=None, cost=Cost.PALLAS, accepts=_chunk_accepts,
    doc="two flash_attention_state calls (prefix-masked + causal chunk) "
        "merged via merge_states — the production form")
registry.register(
    "chunk_attention", "oracle", _chunk_oracle_impl,
    plane="xla", cost=Cost.XLA, accepts=_chunk_accepts,
    doc="contiguous-layout materialising oracle; bitwise-equal to one-shot "
        "prefill on f32 (the chunked-prefill parity test pins this)")


def chunk_attention(q, kp, vp, plen, kc, vc, *, variant=None):
    """One prefill chunk's attention: queries ``q`` (B, H, C, d) at absolute
    positions ``plen + [0, C)`` attend the gathered prefix ``kp``/``vp``
    (B, kv_heads, cap, d; valid length ``plen`` (B,) int32) plus the chunk's
    own keys ``kc``/``vc`` causally.

    Contract: ``plen + C <= cap`` — the scheduler reserves a slot's full
    page span at admission (DESIGN.md §13), so the prefix buffer always has
    room for the chunk (the oracle's contiguous gather relies on it)."""
    return registry.dispatch("chunk_attention", q, kp, vp, plen, kc, vc,
                             variant=variant)
