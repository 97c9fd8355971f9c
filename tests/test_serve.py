"""Serving-engine tests: generation determinism, sampling, engine loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models.lm import LM
from repro.serve import Engine, SamplingParams, sample_token

CFG = ModelConfig(name="stest", family="dense", num_layers=2, d_model=32,
                  vocab_size=64, num_heads=4, num_kv_heads=2, head_dim=8,
                  d_ff=64, dtype="float32", param_dtype="float32",
                  remat=False)


@pytest.fixture(scope="module")
def engine():
    lm = LM(CFG)
    params = lm.init(jax.random.PRNGKey(0))
    return Engine(lm, params, max_len=64,
                  sampling=SamplingParams(greedy=True))


def test_greedy_generation_deterministic(engine):
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    a = engine.generate(prompts, max_new_tokens=8)
    b = engine.generate(prompts, max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (2, 8)
    assert int(a.max()) < 64 and int(a.min()) >= 0


def test_generation_matches_stepwise_forward(engine):
    """Engine output == argmax chain computed with full forwards (the
    KV-cache path must be semantics-preserving end-to-end)."""
    lm, params = engine.lm, engine.params
    prompts = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, 64)
    out = engine.generate(prompts, max_new_tokens=4)
    seq = prompts
    want = []
    for _ in range(4):
        logits, _ = lm.forward(params, seq)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        want.append(int(nxt[0]))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    assert np.asarray(out)[0].tolist() == want


def test_eos_early_stop(engine):
    prompts = jax.random.randint(jax.random.PRNGKey(3), (1, 4), 0, 64)
    # whatever the first generated token is, treat it as EOS
    first = int(np.asarray(engine.generate(prompts, max_new_tokens=1))[0, 0])
    out = engine.generate(prompts, max_new_tokens=6, eos_id=first)
    arr = np.asarray(out)[0]
    assert arr.shape == (6,)
    assert (arr[1:] == first).all() or arr[0] == first   # padded with eos


class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray([[0.1, 3.0, -1.0], [2.0, 0.0, 5.0]])
        out = sample_token(jax.random.PRNGKey(0), logits,
                           SamplingParams(greedy=True))
        assert out.tolist() == [1, 2]

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[10.0, 9.0, -50.0, -50.0]] * 64)
        sp = SamplingParams(temperature=1.0, top_k=2)
        out = sample_token(jax.random.PRNGKey(1), logits, sp)
        assert set(np.asarray(out).tolist()) <= {0, 1}

    def test_temperature_flattens(self):
        logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] * 512)
        hot = sample_token(jax.random.PRNGKey(2), logits,
                           SamplingParams(temperature=0.05))
        cold_unique = len(set(np.asarray(hot).tolist()))
        warm = sample_token(jax.random.PRNGKey(2), logits,
                            SamplingParams(temperature=5.0))
        warm_unique = len(set(np.asarray(warm).tolist()))
        assert cold_unique <= warm_unique


# ---------------------------------------------------------------------------
# continuous-batching tier (DESIGN.md §13)
# ---------------------------------------------------------------------------

import dataclasses

from repro.core import ExecLevel, registry, use_level
from repro.models.lm import LM as _LM  # noqa: F401  (re-exported idiom)
from repro.serve import (ContinuousEngine, Request, Scheduler, make_spec,
                         init_cache_state)

#: paged variant of the module config: small pages so multi-page slots,
#: page striping, and recycling all exercise at test sizes.
PCFG = dataclasses.replace(CFG, name="stest-paged", serve_page_size=8)


def _mk(seed=0):
    lm = LM(PCFG)
    return lm, lm.init(jax.random.PRNGKey(seed))


def _reqs(n, *, seed=0, plen=(3, 12), max_new=5, vocab=64):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, size=int(rng.integers(*plen)))
             .astype(np.int32), max_new) for _ in range(n)]


def _fixed_reference(lm, params, reqs):
    """Per-request greedy outputs through the fixed engine, one at a time
    (no cross-request padding), as the continuous engine's oracle."""
    eng = Engine(lm, params, max_len=64, sampling=SamplingParams(greedy=True))
    return [np.asarray(eng.generate(jnp.asarray(p[None]),
                                    max_new_tokens=m))[0]
            for p, m in reqs]


#: f32 logits of two numerics paths (chunked/paged vs one-shot forward,
#: ring-merged vs chip) differ by summation order only
LOGIT_TOL = 1e-4


def _assert_near_argmax(lm, params, reqs, outs):
    """Teacher-force each request's emitted tokens through the plain
    forward: every token must carry its row's maximum logit to within
    ``LOGIT_TOL``.  Greedy token equality across numerics paths breaks on
    near-ties; this holds each path to the reference logits instead."""
    for i, ((prompt, max_new), got) in enumerate(zip(reqs, outs)):
        got = np.asarray(got, np.int32)
        assert len(got) == max_new, f"request {i}"
        seq = np.concatenate([prompt, got[:-1]]).astype(np.int32)
        logits, _ = lm.forward(params, jnp.asarray(seq[None]))
        rows = np.asarray(logits[0, len(prompt) - 1:], np.float32)
        chosen = rows[np.arange(max_new), got]
        gap = rows.max(axis=1) - chosen
        assert gap.max() <= LOGIT_TOL, (i, gap.max())


class TestPagedCacheSpec:
    def test_spec_shapes_and_striping(self):
        spec = make_spec(PCFG, num_slots=4, max_tokens=60)
        assert spec.page_size == 8
        assert spec.slot_capacity >= 60
        assert spec.num_pages > spec.num_slots * spec.pages_per_slot - 1
        assert spec.pages_for(1) == 1 and spec.pages_for(9) == 2
        assert spec.owner(0) == 0            # ring=1: everything residue 0

    def test_ring_rounding(self):
        spec = make_spec(PCFG, num_slots=2, max_tokens=60, ring=4)
        assert spec.pages_per_slot % 4 == 0
        assert spec.num_pages % 4 == 0
        assert [spec.owner(p) for p in range(4)] == [0, 1, 2, 3]
        lo, hi = spec.shard_range(1)
        assert hi - lo == spec.pages_per_shard

    def test_state_shapes(self):
        spec = make_spec(PCFG, num_slots=2, max_tokens=32)
        state = init_cache_state(PCFG, spec)
        assert state["kpages"].shape == (PCFG.num_layers, spec.num_pages,
                                         PCFG.num_kv_heads, spec.page_size,
                                         PCFG.head_dim)
        assert state["table"].shape == (2, spec.pages_per_slot)
        assert state["lens"].shape == (2,)


class TestScheduler:
    def _sched(self, slots=2, cap=32):
        spec = make_spec(PCFG, num_slots=slots, max_tokens=cap)
        return Scheduler(spec, queue_depth=8)

    def test_admission_blocks_when_batch_full(self):
        """More requests than slots: the queue holds the overflow and
        admission resumes the moment a slot recycles."""
        s = self._sched(slots=2)
        reqs = [Request(rid=i, prompt=np.zeros(4, np.int32), max_new=4)
                for i in range(4)]
        for r in reqs:
            assert s.submit(r)
        assert s.admit_next() is reqs[0]
        assert s.admit_next() is reqs[1]
        assert s.admit_next() is None            # batch full — queue holds
        assert len(s.queue) == 2
        s.recycle(reqs[0].slot)
        got = s.admit_next()
        assert got is reqs[2] and got.slot == reqs[0].slot
        assert s.admit_next() is None

    def test_queue_depth_bounds_submit(self):
        s = self._sched()
        s.queue_depth = 1
        assert s.submit(Request(rid=0, prompt=np.zeros(2, np.int32),
                                max_new=1))
        assert not s.submit(Request(rid=1, prompt=np.zeros(2, np.int32),
                                    max_new=1))

    def test_oversized_request_rejected(self):
        s = self._sched(cap=16)
        with pytest.raises(ValueError):
            s.submit(Request(rid=0, prompt=np.zeros(20, np.int32),
                             max_new=20))

    def test_recycle_reuses_freed_pages(self):
        """A recycled slot's pages go back to the pool and the next
        admission draws from them; the trash page is never handed out."""
        s = self._sched(slots=1)
        free0 = s.num_free_pages
        r1 = Request(rid=0, prompt=np.zeros(12, np.int32), max_new=8)
        s.submit(r1)
        s.admit_next()
        used = {int(g) for g in s.table[0] if g != 0}
        assert used and 0 not in used
        assert s.num_free_pages == free0 - len(used)
        s.recycle(0)
        assert s.num_free_pages == free0
        assert not s.table.any() and not s.lens.any()
        r2 = Request(rid=1, prompt=np.zeros(12, np.int32), max_new=8)
        s.submit(r2)
        s.admit_next()
        reused = {int(g) for g in s.table[0] if g != 0}
        assert reused & used                     # pool reuse, not growth

    def test_page_reservation_covers_generation(self):
        """Admission reserves prompt + max_new up front (decode never
        allocates mid-stream)."""
        s = self._sched(slots=2, cap=32)
        r = Request(rid=0, prompt=np.zeros(9, np.int32), max_new=20)
        s.submit(r)
        s.admit_next()
        allocated = int((s.table[r.slot] != 0).sum())
        assert allocated == s.spec.pages_for(29)


class TestChunkedPrefill:
    def test_chunked_equals_oneshot_bitwise_f32(self):
        """Chunked prefill is *bitwise* one-shot prefill on f32 under the
        XLA plane: the oracle's contiguous layout folds the identical
        softmax terms in the identical order regardless of the split."""
        lm, params = _mk()
        spec = make_spec(PCFG, num_slots=2, max_tokens=32)
        sched = Scheduler(spec, queue_depth=4)
        prompt = np.asarray(
            np.random.default_rng(5).integers(0, 64, 16), np.int32)
        sched.submit(Request(rid=0, prompt=prompt, max_new=4))
        sched.admit_next()
        base = init_cache_state(PCFG, spec)
        base["table"] = jnp.asarray(sched.table)

        with registry.use_backend("xla"):
            sel = registry.select("chunk_attention",
                                  jnp.zeros((1, 4, 4, 8), jnp.float32),
                                  jnp.zeros((1, 2, 32, 8), jnp.float32),
                                  jnp.zeros((1, 2, 32, 8), jnp.float32),
                                  jnp.zeros((1,), jnp.int32),
                                  jnp.zeros((1, 2, 4, 8), jnp.float32),
                                  jnp.zeros((1, 2, 4, 8), jnp.float32))
            assert sel.name == "oracle"
            lg_mono, st_mono = lm.prefill_chunk(
                params, dict(base), jnp.asarray(prompt), np.int32(0),
                np.int32(0), np.int32(16))
            st = dict(base)
            for s0 in range(0, 16, 4):
                lg_chunk, st = lm.prefill_chunk(
                    params, st, jnp.asarray(prompt[s0:s0 + 4]), np.int32(0),
                    np.int32(s0), np.int32(4))

        np.testing.assert_array_equal(np.asarray(lg_mono),
                                      np.asarray(lg_chunk))
        np.testing.assert_array_equal(np.asarray(st_mono["lens"]),
                                      np.asarray(st["lens"]))
        np.testing.assert_array_equal(np.asarray(st_mono["kpages"]),
                                      np.asarray(st["kpages"]))

    def test_uneven_final_chunk_padding_is_inert(self):
        """A padded final chunk (valid_len < C) writes only to the trash
        page and yields the same logits as an exact-fit chunking."""
        lm, params = _mk()
        spec = make_spec(PCFG, num_slots=2, max_tokens=32)
        sched = Scheduler(spec, queue_depth=4)
        prompt = np.asarray(
            np.random.default_rng(6).integers(0, 64, 10), np.int32)
        sched.submit(Request(rid=0, prompt=prompt, max_new=4))
        sched.admit_next()
        base = init_cache_state(PCFG, spec)
        base["table"] = jnp.asarray(sched.table)

        with registry.use_backend("xla"):
            lg_exact, _ = lm.prefill_chunk(
                params, dict(base), jnp.asarray(prompt), np.int32(0),
                np.int32(0), np.int32(10))
            st = dict(base)
            padded = np.zeros(6, np.int32)
            padded[:2] = prompt[8:]
            _, st = lm.prefill_chunk(params, st, jnp.asarray(prompt[:8]),
                                     np.int32(0), np.int32(0), np.int32(8))
            lg_pad, st = lm.prefill_chunk(params, st, jnp.asarray(padded),
                                          np.int32(0), np.int32(8),
                                          np.int32(2))
        np.testing.assert_array_equal(np.asarray(lg_exact),
                                      np.asarray(lg_pad))
        assert int(st["lens"][0]) == 10


class TestContinuousEngine:
    def test_matches_fixed_engine_per_request(self):
        """End-to-end continuous generate (tiny): chunked prefill + paged
        decode and the fixed engine both emit, per request, the greedy
        tokens of the one-shot forward's logits (to ``LOGIT_TOL``) — so
        they agree wherever the top two logits are not a near-tie."""
        lm, params = _mk()
        reqs = _reqs(4, max_new=5)
        want = _fixed_reference(lm, params, reqs)
        eng = ContinuousEngine(lm, params, num_slots=2, max_len=64,
                               chunk_size=4,
                               sampling=SamplingParams(greedy=True))
        got = eng.serve(reqs)
        _assert_near_argmax(lm, params, reqs, want)
        _assert_near_argmax(lm, params, reqs, got)

    def test_recycling_across_many_admissions(self):
        """3x more requests than slots: every slot recycles repeatedly and
        outputs stay per-request correct."""
        lm, params = _mk()
        base = _reqs(3, max_new=4)
        want = _fixed_reference(lm, params, base)
        reqs = [base[i % 3] for i in range(9)]
        eng = ContinuousEngine(lm, params, num_slots=3, max_len=64,
                               chunk_size=4,
                               sampling=SamplingParams(greedy=True))
        got = eng.serve(reqs)
        for i, g in enumerate(got):
            assert g.tolist() == want[i % 3].tolist(), f"request {i}"

    def test_decode_never_retraces(self):
        """Admissions and recycles rewrite table/lens contents only: one
        compiled decode step serves the engine's whole lifetime."""
        lm, params = _mk()
        eng = ContinuousEngine(lm, params, num_slots=2, max_len=64,
                               chunk_size=4,
                               sampling=SamplingParams(greedy=True))
        eng.serve(_reqs(5, seed=1, max_new=3))
        eng.serve(_reqs(3, seed=2, max_new=6))
        assert eng._decode._cache_size() == 1
        assert eng._prefill_chunk._cache_size() == 1

    def test_eos_never_emits_past_eos(self):
        """The async (lagged-window) EOS check must trim exactly at the
        first eos even though the engine only *discovers* it windows later:
        no eos token and nothing after it ever reaches the output."""
        lm, params = _mk()
        reqs = _reqs(4, seed=3, max_new=24)      # crosses EOS_CHECK_EVERY
        want = _fixed_reference(lm, params, reqs)
        # choose an eos id each stream actually emits mid-run when possible
        eos = int(want[0][2])
        eng = ContinuousEngine(lm, params, num_slots=2, max_len=64,
                               chunk_size=4,
                               sampling=SamplingParams(greedy=True))
        got = eng.serve(reqs, eos_id=eos)
        for i, (w, g) in enumerate(zip(want, got)):
            wl = w.tolist()
            trimmed = wl[:wl.index(eos)] if eos in wl else wl
            assert g.tolist() == trimmed, f"request {i}"
            assert eos not in g.tolist()

    def test_slot_capacity_never_overflows(self):
        """Budget-exact countdown: a stream that fills its slot exactly to
        capacity completes without writing past its reserved pages."""
        lm, params = _mk()
        spec_cap = 32
        prompt = np.arange(20, dtype=np.int32) % 64
        eng = ContinuousEngine(lm, params, num_slots=2, max_len=spec_cap,
                               chunk_size=8,
                               sampling=SamplingParams(greedy=True))
        got = eng.serve([(prompt, 12)])          # 20 + 12 == capacity
        assert len(got[0]) == 12
        assert eng.sched.num_free_pages == sum(
            len(p) for p in eng.sched.free_pages)
        assert not eng.sched.running


class TestRingShardedDecode:
    """The paged decode's mesh story: ring-striped pages + per-shard
    flash partials merged with the §10 psum dual == the chip path."""

    def test_engine_ring_decode_matches_chip_mesh8(self, mesh8):
        lm, params = _mk()
        reqs = _reqs(4, seed=4, max_new=6)
        chip = ContinuousEngine(lm, params, num_slots=2, max_len=64,
                                chunk_size=4,
                                sampling=SamplingParams(greedy=True))
        want = chip.serve(reqs)
        with use_level(ExecLevel.O3, mesh8):
            ring = ContinuousEngine(lm, params, num_slots=2, max_len=64,
                                    chunk_size=4,
                                    sampling=SamplingParams(greedy=True))
        assert ring.spec.ring == 8
        got = ring.serve(reqs)
        _assert_near_argmax(lm, params, reqs, want)
        _assert_near_argmax(lm, params, reqs, got)
        assert ring._decode._cache_size() == 1

    def test_paged_attention_op_ring_matches_chip_mesh222(self, mesh222):
        """Op-level numerics on the O4 mesh (pod x data ring, width 4):
        per-shard prefix-masked partials + psum merge vs the chip gather."""
        from repro.distributed.collectives import ring_plan

        W = ring_plan(mesh222).size
        assert W == 4
        spec = make_spec(PCFG, num_slots=3, max_tokens=48, ring=W)
        sched = Scheduler(spec, queue_depth=4)
        lens_want = [37, 11, 0]
        for rid, tot in enumerate(t for t in lens_want if t):
            sched.submit(Request(rid=rid,
                                 prompt=np.zeros(tot, np.int32), max_new=0))
            assert sched.admit_next() is not None
        sched.lens[:] = lens_want

        rng = np.random.default_rng(11)
        B, H, HK, D = 3, 4, 2, 8
        q = jnp.asarray(rng.standard_normal((B, H, 1, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal(
            (spec.num_pages, HK, spec.page_size, D)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal(
            (spec.num_pages, HK, spec.page_size, D)), jnp.float32)
        table = jnp.asarray(sched.table)
        lens = jnp.asarray(sched.lens)

        chip = registry.dispatch("paged_attention", q, kp, vp, table, lens)
        with use_level(ExecLevel.O4, mesh222):
            sel = registry.select("paged_attention", q, kp, vp, table, lens)
            assert sel.name == "ring" and sel.scope == "mesh"
            ring = registry.dispatch("paged_attention", q, kp, vp, table,
                                     lens)
        # slots with lens == 0 are garbage in both paths (differently);
        # the engine never reads them
        for b, n in enumerate(lens_want):
            if n == 0:
                continue
            np.testing.assert_allclose(np.asarray(ring[b]),
                                       np.asarray(chip[b]),
                                       rtol=1e-5, atol=1e-5)
