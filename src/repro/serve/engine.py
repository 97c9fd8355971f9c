"""Serving engines: fixed-slot batch + the continuous-batching tier.

:class:`Engine` is the inference counterpart of the trainer: it owns the
jit'd ``prefill`` / ``decode_step`` and exposes ``generate`` for one
batched request.  Its batch is fixed-slot — every prompt pads to the batch
max, every slot runs to the batch's ``max_new_tokens`` — which is exactly
the shape the paper's throughput argument warns about: peak kernel speed
buried under pipeline stalls.

:class:`ContinuousEngine` (DESIGN.md §13) is the production shape: a
paged, optionally ring-sharded KV cache (``serve/kvcache.py``), a
host-side scheduler with an admission queue and device-side slot
recycling (``serve/scheduler.py``), chunked prefill interleaved into the
decode loop so a long prompt never stalls in-flight streams, and an
async-lagged EOS check.  The jit'd one-token ``decode_step_paged``
signature is admission-stable — recycling rewrites page-table *contents*,
never shapes — so the decode loop is traced exactly once per engine.

serve_step (the dry-run artifact for decode_* / long_* shapes) is exactly
``decode_step``: one new token against a KV cache of ``seq_len``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import execlevel, registry
from repro.kernels.flash_attention import NEG_INF
from repro.models.lm import LM
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

Params = dict[str, Any]

__all__ = ["SamplingParams", "Engine", "ContinuousEngine", "ServeStats",
           "sample_token"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_k: int = 0              # 0 = no top-k
    greedy: bool = False
    #: What early-stopped slots pad with when no ``eos_id`` is given —
    #: explicit so callers can distinguish padding from a real token 0.
    pad_id: int = 0


def sample_token(key, logits: jax.Array, sp: SamplingParams) -> jax.Array:
    """logits (B, V) -> tokens (B,) int32."""
    if sp.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / max(sp.temperature, 1e-6)
    if sp.top_k:
        kth = jax.lax.top_k(logits, sp.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


class Engine:
    """Owns compiled prefill/decode; host-side loop drives generation."""

    def __init__(self, lm: LM, params: Params, *, max_len: int = 2048,
                 sampling: SamplingParams = SamplingParams(greedy=True),
                 donate_cache: bool = True):
        self.lm = lm
        self.params = params
        self.max_len = max_len
        self.sampling = sampling
        # Pin the kernel plane the registry resolves *now*: prefill/decode
        # trace lazily on first call, and an ambient plane flip mid-service
        # must not retrace (or worse, split) the compiled decode loop.
        self.active_backend = registry.resolve_backend()
        # Pin the execution level/mesh the same way: a long-prompt prefill
        # constructed under use_level(O3/O4) shards the sequence over the
        # ring (flash_attention/'ring', DESIGN.md §10) on every call, not
        # just while the constructor's context happens to be open.  Decode
        # runs *outside* it — one token against a resident KV cache is
        # chip-local by construction, and must never retarget mid-stream.
        self.active_level = execlevel.current()

        self._prefill = jax.jit(
            functools.partial(lm.prefill, max_len=max_len))

        def decode_fn(params, cache, tokens, key):
            logits, cache = lm.decode_step(params, cache, tokens)
            key, sub = jax.random.split(key)
            nxt = sample_token(sub, logits, self.sampling)
            return cache, nxt, key

        # donating the cache buffer keeps decode allocation-free
        self._decode = jax.jit(
            decode_fn, donate_argnums=(1,) if donate_cache else ())

    def generate(self, tokens: jax.Array, *, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None, seed: int = 0,
                 frontend_embeds: Optional[jax.Array] = None) -> jax.Array:
        """tokens (B, S) prompt -> (B, max_new_tokens) generated ids."""
        with registry.use_backend(self.active_backend):
            return self._generate(tokens, max_new_tokens=max_new_tokens,
                                  eos_id=eos_id, seed=seed,
                                  frontend_embeds=frontend_embeds)

    #: decode steps between host-side all-done checks.  Each check reads a
    #: device flag; per-token checking made every step blocking.  ``done``
    #: is tracked device-side in between, and finished slots emit eos, so
    #: the only cost of a coarser period is up to EOS_CHECK_EVERY-1 extra
    #: (cheap, fully batched) decode steps.
    EOS_CHECK_EVERY = 8

    def _generate(self, tokens, *, max_new_tokens, eos_id, seed,
                  frontend_embeds):
        B = tokens.shape[0]
        lvl = self.active_level
        with execlevel.use_level(lvl.level, lvl.mesh):
            logits, cache = self._prefill(self.params, tokens,
                                          frontend_embeds)
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        nxt = sample_token(sub, logits, self.sampling)

        outs = [nxt]
        done = jnp.zeros((B,), bool)
        if eos_id is not None:
            done = nxt == eos_id
        # Async EOS: the boundary check reads the done-flag captured at the
        # *previous* window, whose device computation finished a full
        # window ago — the host never blocks on in-flight decode steps.
        # Worst case one extra window of (frozen, eos-emitting) steps runs;
        # outputs are identical because finished slots emit eos anyway.
        pending_done = None
        for step in range(max_new_tokens - 1):
            if (eos_id is not None and
                    step % self.EOS_CHECK_EVERY == self.EOS_CHECK_EVERY - 1):
                if (pending_done is not None
                        and bool(np.asarray(pending_done).all())):
                    break
                pending_done = done
            cache, nxt, key = self._decode(self.params, cache,
                                           nxt[:, None], key)
            if eos_id is not None:
                nxt = jnp.where(done, eos_id, nxt)   # freeze finished slots
                done = done | (nxt == eos_id)
            outs.append(nxt)
        out = jnp.stack(outs, axis=1)
        if out.shape[1] < max_new_tokens:   # early-stopped: pad
            pad = jnp.full((B, max_new_tokens - out.shape[1]),
                           eos_id if eos_id is not None
                           else self.sampling.pad_id, jnp.int32)
            out = jnp.concatenate([out, pad], axis=1)
        return out


@dataclasses.dataclass
class ServeStats:
    """Per-iteration telemetry from :meth:`ContinuousEngine.serve`."""
    iter_times: list        # wall seconds per loop iteration
    tokens_per_iter: list   # tokens emitted (decode + prefill-completions)
    occupancy: list         # active-slot fraction per iteration
    token_latencies: list   # per emitted token: its iteration's wall time
    first_token_times: list  # per request: submit -> first token seconds


class ContinuousEngine:
    """Continuous batching over a paged (optionally ring-sharded) KV cache.

    The host loop interleaves, per iteration: admission from the queue,
    one prefill chunk for the oldest prefilling slot, one batched decode
    step over the active slots, and (every ``EOS_CHECK_EVERY`` iterations)
    the async EOS/output demux of the *previous* window's device refs.
    Slot recycling is device-side: a finished slot's pages return to the
    free pools and the next request is admitted by uploading new
    table/lens *contents* — the decode step never retraces
    (``engine._decode._cache_size() == 1`` for the life of the engine).
    """

    EOS_CHECK_EVERY = 8

    def __init__(self, lm: LM, params: Params, *, num_slots: int = 8,
                 max_len: int = 2048, chunk_size: int = 32,
                 num_pages: Optional[int] = None,
                 sampling: SamplingParams = SamplingParams(greedy=True),
                 queue_depth: Optional[int] = None,
                 heartbeats=None, worker: int = 0):
        from repro.distributed.collectives import ambient_ring_plan
        from repro.runtime.fault_tolerance import HeartbeatStore
        from repro.serve.kvcache import init_cache_state, make_spec
        from repro.serve.scheduler import Scheduler

        self.lm = lm
        self.params = params
        self.sampling = sampling
        self.chunk_size = chunk_size
        # Liveness plane (DESIGN.md §14): one beat per host-loop iteration
        # carrying (step, occupancy), against the same store/Monitor
        # protocol the trainer posts to — a stalled serve loop goes DEAD on
        # the coordinator exactly like a stalled train step.
        self.heartbeats = heartbeats if heartbeats is not None \
            else HeartbeatStore()
        self.worker = worker
        self.active_backend = registry.resolve_backend()
        self.active_level = execlevel.current()

        with execlevel.use_level(self.active_level.level,
                                 self.active_level.mesh):
            plan = ambient_ring_plan()
        self._plan = plan
        ring = plan.size if plan is not None else 1
        cfg = lm.cfg
        self.spec = make_spec(cfg, num_slots=num_slots, max_tokens=max_len,
                              num_pages=num_pages, ring=ring)
        self.state = init_cache_state(cfg, self.spec)
        if plan is not None:
            # Commit the pools to their steady-state layout up front: the
            # page axis striped over the ring, table/lens replicated.  The
            # compiled decode step would settle here anyway — committing
            # from call one keeps its jit cache at a single entry.
            from jax.sharding import NamedSharding, PartitionSpec as P
            entry = plan.spec_entry()
            shard = NamedSharding(plan.mesh, P(None, entry))
            rep = NamedSharding(plan.mesh, P())
            self.state["kpages"] = jax.device_put(self.state["kpages"], shard)
            self.state["vpages"] = jax.device_put(self.state["vpages"], shard)
            self.state["table"] = jax.device_put(self.state["table"], rep)
            self.state["lens"] = jax.device_put(self.state["lens"], rep)
        self.sched = Scheduler(
            self.spec, queue_depth if queue_depth is not None
            else cfg.serve_queue_depth)

        def decode_fn(params, state, tokens, active, key):
            logits, state = lm.decode_step_paged(params, state,
                                                 tokens[:, None], active)
            key, sub = jax.random.split(key)
            nxt = sample_token(sub, logits, self.sampling)
            # frozen slots pass their token through: their logits are
            # garbage (trash-page write, stale length) by construction
            nxt = jnp.where(active > 0, nxt, tokens)
            return state, nxt, key

        self._decode = jax.jit(decode_fn, donate_argnums=(1,))
        self._prefill_chunk = jax.jit(lm.prefill_chunk, donate_argnums=(1,))

        def sample1(key, logits):
            key, sub = jax.random.split(key)
            tok = sample_token(sub, logits[None, :], self.sampling)[0]
            return key, tok

        self._sample1 = jax.jit(sample1)
        # no donation: ``cur`` aliases the previous decode's ``nxt``, whose
        # ref may still sit in a pending output window
        self._set_tok = jax.jit(lambda cur, slot, tok: cur.at[slot].set(tok))

    # -- the serve loop -----------------------------------------------------

    def serve(self, requests: Sequence[tuple], *,
              eos_id: Optional[int] = None, seed: int = 0,
              arrival: Optional[Sequence[float]] = None,
              collect_stats: bool = False):
        """Run ``requests`` — a sequence of ``(prompt, max_new)`` pairs —
        to completion under continuous batching.

        ``arrival`` optionally offsets each request's submission by wall
        seconds from loop start (the offered-QPS knob of the load
        benchmark).  Returns a list of per-request generated-token arrays
        (trimmed at the first eos), or ``(outputs, ServeStats)`` with
        ``collect_stats``."""
        from repro.serve.scheduler import Request

        reqs = [Request(rid=i, prompt=np.asarray(p, np.int32).reshape(-1),
                        max_new=int(m)) for i, (p, m) in enumerate(requests)]
        lvl = self.active_level
        with registry.use_backend(self.active_backend), \
                execlevel.use_level(lvl.level, lvl.mesh):
            return self._serve(reqs, eos_id=eos_id, seed=seed,
                               arrival=arrival, collect_stats=collect_stats)

    def _upload_tables(self):
        # upload snapshots: a host-to-device transfer may read (or, on the
        # CPU backend, alias) its numpy buffer after the call returns, and
        # the scheduler mutates these mirrors in place
        self.state = dict(self.state)
        table = jnp.asarray(self.sched.table.copy())
        lens = jnp.asarray(self.sched.lens.copy())
        if self._plan is not None:
            # match the committed replicated layout (see __init__) so the
            # upload never perturbs the decode step's jit cache
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(self._plan.mesh, P())
            table = jax.device_put(table, rep)
            lens = jax.device_put(lens, rep)
        self.state["table"] = table
        self.state["lens"] = lens

    def _serve(self, reqs, *, eos_id, seed, arrival, collect_stats):
        sched, spec = self.sched, self.spec
        B = spec.num_slots
        C = self.chunk_size
        key = jax.random.PRNGKey(seed)
        cur = jnp.zeros((B,), jnp.int32)          # device-resident next tokens

        outputs = {r.rid: [] for r in reqs}
        stats = ServeStats([], [], [], [], [])
        # host mirrors advanced in lockstep with the device (identical
        # integer updates; uploads after admit/recycle only swap contents)
        active_np = np.zeros((B,), np.int32)
        # device copy of the active mask, refreshed only on lifecycle
        # events (activation / release) — not re-uploaded every step
        active_dev = [jnp.asarray(active_np.copy())]
        budget = np.zeros((B,), np.int64)
        gen = np.zeros((B,), np.int64)            # per-slot admission epoch
        live: dict[tuple, Any] = {}               # (slot, gen) -> Request
        prefilling: list = []                     # slots in PREFILL, FIFO
        # async output/EOS demux: device refs batch into windows; the
        # boundary processes the *previous* window (its device work
        # finished a window ago, so np.asarray does not block the pipe)
        pending_old: list = []
        pending_cur: list = []

        to_submit = list(reqs)
        t0 = time.monotonic()
        if arrival is None:
            arrival = [0.0] * len(reqs)

        def release(slot):
            """Return a slot's pages and free it for re-admission.  Eager:
            the single device stream executes already-enqueued decode reads
            before any later write into the reused pages, so pending output
            refs stay valid.  Token *attribution* stays lagged via ``live``."""
            sched.recycle(slot)
            active_np[slot] = 0
            active_dev[0] = jnp.asarray(active_np.copy())
            if slot in prefilling:
                prefilling.remove(slot)
            self._upload_tables()

        def handle_token(slot, g, tok):
            req = live.get((slot, g))
            if req is None:                       # post-eos stragglers
                return
            if req.first_token_t == 0.0:
                req.first_token_t = time.monotonic()
                ttft = req.first_token_t - req.submit_t
                stats.first_token_times.append(ttft)
                obs_metrics.METRICS.histogram("serve.ttft_s").record(ttft)
            if eos_id is not None and tok == eos_id:
                live.pop((slot, g))
                # the slot was decoding past the (lagged) eos discovery;
                # release it unless the budget path already recycled it
                if sched.running.get(slot) is req:
                    release(slot)
                return
            outputs[req.rid].append(tok)

        def process(bucket):
            for entry in bucket:
                kind = entry[0]
                if kind == "p":                   # prefill's first token
                    _, slot, g, ref = entry
                    handle_token(slot, g, int(np.asarray(ref)))
                elif kind == "d":                 # one decode step
                    _, ref, gens = entry
                    arr = np.asarray(ref)
                    for slot in np.nonzero(gens)[0]:
                        handle_token(int(slot), int(gens[slot]),
                                     int(arr[slot]))
                else:                             # attribution complete
                    _, slot, g = entry
                    live.pop((slot, g), None)
            bucket.clear()

        it = 0
        tracer = obs_trace.TRACER
        metrics = obs_metrics.METRICS
        while to_submit or sched.queue or sched.running \
                or pending_old or pending_cur:
            t_iter = time.monotonic()
            emitted = 0

            with tracer.span("serve.admit", cat="serve"):
                # 1. submissions whose arrival time has come
                while to_submit \
                        and (t_iter - t0) >= arrival[to_submit[0].rid]:
                    req = to_submit.pop(0)
                    req.submit_t = time.monotonic()
                    assert sched.submit(req), "admission queue overflow"

                # 2. admission — rewrites table/lens contents, never shapes
                admitted = False
                while (req := sched.admit_next()) is not None:
                    gen[req.slot] += 1
                    live[(req.slot, gen[req.slot])] = req
                    prefilling.append(req.slot)
                    admitted = True
                if admitted:
                    self._upload_tables()

            # 3. one prefill chunk for the oldest prefilling slot
            if prefilling:
                slot = prefilling[0]
                req = live[(slot, gen[slot])]
                valid = min(C, req.prompt_len - req.prefilled)
                with tracer.span("serve.prefill_chunk", cat="serve",
                                 slot=slot, offset=req.prefilled,
                                 valid=valid):
                    chunk = np.zeros((C,), np.int32)
                    chunk[:valid] = req.prompt[req.prefilled:
                                               req.prefilled + valid]
                    logits, self.state = self._prefill_chunk(
                        self.params, self.state, jnp.asarray(chunk),
                        np.int32(slot), np.int32(req.prefilled),
                        np.int32(valid))
                req.prefilled += valid
                sched.lens[slot] = req.prefilled      # lockstep mirror
                if req.prefilled >= req.prompt_len:
                    prefilling.pop(0)
                    key, tok = self._sample1(key, logits)
                    cur = self._set_tok(cur, np.int32(slot), tok)
                    pending_cur.append(("p", slot, int(gen[slot]), tok))
                    emitted += 1
                    budget[slot] = req.max_new - 1
                    if budget[slot] > 0:
                        active_np[slot] = 1
                        active_dev[0] = jnp.asarray(active_np.copy())
                    else:                 # budget spent: free the slot now
                        release(slot)
                        pending_cur.append(("drain", slot, int(gen[slot])))

            # 4. one batched decode step over the active slots
            n_active = int((active_np > 0).sum())
            if n_active:
                with tracer.span("serve.decode", cat="serve",
                                 active=n_active):
                    self.state, nxt, key = self._decode(
                        self.params, self.state, cur, active_dev[0], key)
                cur = nxt
                snapshot = np.where(active_np > 0, gen, 0)
                pending_cur.append(("d", nxt, snapshot))
                on = active_np > 0
                emitted += n_active
                sched.lens[on] += 1                   # lockstep mirror
                budget[on] -= 1
                # budget exhaustion is host-exact: release the slot *now*
                # (re-admission next iteration), leaving only a lagged
                # attribution marker for the window demux
                for slot in np.nonzero(on & (budget <= 0))[0]:
                    release(int(slot))
                    pending_cur.append(("drain", int(slot),
                                        int(gen[slot])))

            # 5. window boundary: demux the previous window's device refs
            it += 1
            if it % self.EOS_CHECK_EVERY == 0:
                with tracer.span("serve.demux", cat="serve",
                                 window=len(pending_old)):
                    process(pending_old)
                pending_old, pending_cur = pending_cur, pending_old

            dt = time.monotonic() - t_iter
            occ = n_active / B
            if emitted:
                metrics.counter("serve.tokens").inc(emitted)
                metrics.histogram("serve.token_latency_s").record(
                    dt, n=emitted)
            if occ > 0:
                # distribution of the *decoding* occupancy per iteration;
                # the scheduler exports the instantaneous gauge
                metrics.histogram("serve.occupancy_dist").record(occ)
            self.heartbeats.post(self.worker, it, occupancy=occ)
            if collect_stats:
                stats.iter_times.append(dt)
                stats.tokens_per_iter.append(emitted)
                stats.occupancy.append(occ)
                stats.token_latencies.extend([dt] * emitted)

            if not sched.running and not pending_old and not pending_cur \
                    and (to_submit or sched.queue):
                metrics.counter("serve.idle_s").inc(0.0005)
                time.sleep(0.0005)        # idle: waiting on arrivals

        process(pending_old)
        process(pending_cur)
        outs = [np.asarray(outputs[r.rid], np.int32) for r in reqs]
        if collect_stats:
            return outs, stats
        return outs
