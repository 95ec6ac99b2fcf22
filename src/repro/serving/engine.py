"""Continuous-batching serve engine on ONE persistent STF task graph.

The production serving tier (ROADMAP "millions of users" axis): requests
join and leave the decode batch mid-flight — there is no generation-wide
barrier anywhere.  Every engine iteration inserts chained codelets into a
single long-lived :class:`SpTaskGraph` owned by the engine (not one graph
per step); the WRITE chain on the shared batch-state cell serializes what
must be serialized and nothing else:

    decode      write(state)  — one fused decode step + per-request
                                sampling for the whole batch
    collect     read(state)   — account fed tokens into the paged pool
                                (block appends, copy-on-write, preemption),
                                emit finished sequences, free slots
    prefill     write(out)    — prompt prefill for ONE admitted request;
                                touches no shared state, so it runs
                                concurrently with in-flight decode steps
    install     write(state), read(out)
                              — scatter the prefilled KV into the slot
    restore     write(state)  — prefix-cache hit / resume: scatter saved
                                block payloads instead of recomputing

A new request's prefill therefore starts the moment it is admitted, while
other sequences keep decoding — the continuous-batching property the
benchmark (`benchmarks/serving_bench.py`) measures against a drain-barrier
baseline.

Memory is managed by the paged KV cache (``kvcache.py``): block tables per
sequence, prefix sharing with refcounts + copy-on-write, and deterministic
block-granularity LRU eviction — the paper's §4.3 device-memory policy at
the level the serving tier actually manages.  Admission control and
backpressure live in ``scheduler.py``.

Threading model: ``submit()`` is thread-safe; ``step()``/``run_until_drained``
must be driven from one thread (the planner mutates pool state with the
graph drained).
"""
from __future__ import annotations

import itertools
import math
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    SpComputeEngine,
    SpData,
    SpSpeculativeModel,
    SpTaskGraph,
    SpWorkerTeamBuilder,
    graph_scope,
    sp_task,
)
from repro.models import cache_layout, decode_step, init_cache, prefill
from repro.models.config import ArchConfig
from repro.runtime.serve import (
    concat_cache_rows,
    extract_cache_rows,
    insert_cache_rows,
    prime_cache,
)
from repro.serving.kvcache import KVPagePool, PageError
from repro.serving.scheduler import Admission, ServeScheduler

_req_ids = itertools.count()

#: jitted (decode, prefill) per config — shared across engines so repeated
#: engine builds (tests, benchmark modes) reuse XLA compilation caches
_JIT_CACHE: dict = {}


def _jitted_steps(cfg):
    key = repr(cfg)
    fns = _JIT_CACHE.get(key)
    if fns is None:

        def serve_decode(p, t, c, pos):
            return decode_step(p, t, c, pos, cfg)

        def serve_prefill(p, b):
            return prefill(p, b, cfg)

        fns = (jax.jit(serve_decode, donate_argnums=(2,)), jax.jit(serve_prefill))
        _JIT_CACHE[key] = fns
    return fns


def _jitted_serve_ops(cfg, max_seq: int):
    """Admission hot path, fused into XLA: (prefill → prime) in one call and
    the slot install scatter in another.  Op-by-op these cost ~10 ms per
    admission — more than several decode steps — which would make continuous
    admission slower than the drain barrier it replaces."""
    key = (repr(cfg), max_seq)
    fns = _JIT_CACHE.get(key)
    if fns is None:

        def prefill_prime(p, b):
            logits, caches = prefill(p, b, cfg)
            return logits[:, -1], prime_cache(cfg, caches, b["tokens"].shape[1], max_seq)

        def install(full, one, tok, slot, pending):
            caches = jax.tree.map(
                lambda f, o: f.at[:, slot].set(o[:, 0].astype(f.dtype)), full, one
            )
            return caches, tok.at[slot, 0].set(pending)

        fns = (
            jax.jit(prefill_prime),
            jax.jit(install, donate_argnums=(0,)),
        )
        _JIT_CACHE[key] = fns
    return fns


@dataclass
class Request:
    """One serving request.  ``temperature == 0`` (default) decodes greedily;
    otherwise tokens are drawn from the temperature-scaled, top-k-filtered
    distribution with a PRNG stream seeded per request (``seed``) and folded
    per step — two runs with the same seed produce the same tokens.

    ``deadline`` is an absolute ``time.perf_counter()`` timestamp: once it
    passes, the request is shed from the queue or cancelled mid-decode
    (KV blocks released) rather than finishing work nobody will read.
    ``reject_reason`` says why a rejected request was turned away:
    ``"queue_full"``, ``"shed"``, or ``"deadline"``.

    ``speculative`` requests decode through draft/verify/commit rounds when
    the engine has a draft model; ``out_tokens``/``t_tokens``/``on_token``
    only ever see *committed* tokens (drafted-but-unverified tokens live in
    the speculation machinery's uncommitted state)."""

    prompt: np.ndarray  # (L,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0  # 0 = no top-k filter
    seed: int = 0
    deadline: Optional[float] = None  # absolute perf_counter seconds
    speculative: bool = False
    on_token: Optional[callable] = None  # per committed token, engine thread
    req_id: int = field(default_factory=lambda: next(_req_ids))
    out_tokens: list = field(default_factory=list)
    done: bool = False
    rejected: bool = False
    reject_reason: Optional[str] = None
    cancelled: bool = False
    # continuous-batching bookkeeping
    pending_tok: Optional[int] = None  # sampled (or prompt tail) token not yet fed
    admit_order: int = -1
    preemptions: int = 0
    # speculative-decoding telemetry
    spec_rounds: int = 0
    spec_accepted: int = 0
    # latency telemetry (perf_counter seconds), consumed by the load generator
    t_arrival: Optional[float] = None
    t_first: Optional[float] = None
    t_tokens: list = field(default_factory=list)

    def stream(self, poll: float = 0.001, timeout: Optional[float] = None):
        """Incremental iterator over committed tokens: yields each token of
        ``out_tokens`` as it lands, returning when the request finishes.
        Drive it from a different thread than the engine loop (the engine
        must keep stepping for tokens to arrive); ``out_tokens`` is
        append-only, so a plain cursor is race-free under the GIL."""
        i = 0
        t0 = time.perf_counter()
        while True:
            while i < len(self.out_tokens):
                yield self.out_tokens[i]
                i += 1
            if self.done:
                return
            if timeout is not None and time.perf_counter() - t0 > timeout:
                raise TimeoutError(f"request {self.req_id}: stream timed out")
            time.sleep(poll)

    def cancel(self) -> None:
        """Withdraw the request.  Safe from any thread: the flag is acted on
        at the next scheduling point — a waiting request is dropped by
        ``plan()``, a running one is evicted by the collect codelet with its
        KV blocks released mid-decode."""
        self.cancelled = True


# ---------------------------------------------------------------------------
# Codelets (``eng`` is the ServeEngine, bound as a static parameter).
# ---------------------------------------------------------------------------

@sp_task(write=("state",), name="serve.decode", cost=10.0)
def _decode_codelet(state, *, eng):
    if not eng._slot_req:
        return
    st = state.value
    logits, new_caches = eng._decode(
        eng.params, st["tok"], st["caches"], jnp.asarray(eng._pos)
    )
    toks = eng._sample_batch(logits[:, 0])
    state.value = {"caches": new_caches, "tok": toks[:, None]}


@sp_task(read=("state",), name="serve.collect")
def _collect_codelet(state, *, eng):
    if not eng._slot_req:
        return
    eng._caches = state["caches"]
    eng._last_tok = state["tok"]
    toks = np.asarray(state["tok"][:, 0])
    now = time.perf_counter()
    for slot in sorted(eng._slot_req):
        req = eng._slot_req.get(slot)
        if req is None:  # preempted as a victim earlier in this loop
            continue
        if req.cancelled:
            eng._cancel_slot(slot, reason=None)
            continue
        if req.deadline is not None and now > req.deadline:
            eng._cancel_slot(slot, reason="deadline")
            continue
        # the token decoded this step was ``pending_tok``; its KV row now
        # exists, so account it into the block table (may COW / preempt)
        try:
            eng.pool.append_token(req.req_id, req.pending_tok)
        except PageError:
            if not eng._preempt_for(slot):
                eng._preempt(slot)  # nothing else to preempt: park itself
                continue
            eng.pool.append_token(req.req_id, req.pending_tok)
        eng._pos[slot] += 1
        new = int(toks[slot])
        req.out_tokens.append(new)
        req.pending_tok = new
        if req.t_first is None:
            req.t_first = now
        req.t_tokens.append(now)
        eng._emit_token(req, new)
        if len(req.out_tokens) >= req.max_new_tokens or eng._pos[slot] >= eng.max_seq:
            eng._finish(slot)


@sp_task(write=("out",), name="serve.prefill", cost=5.0)
def _prefill_codelet(out, *, eng, req, sample_first):
    """Prefill one request.  No access to the shared batch state — it runs
    concurrently with whatever decode steps are in flight."""
    fed = req.prompt if sample_first else np.concatenate(
        [req.prompt, np.asarray(req.out_tokens[:-1], np.int32)]
    )
    prompt = np.asarray(fed, np.int32)[None, :]
    logits_last, primed = eng._prefill_prime(eng.params, {"tokens": jnp.asarray(prompt)})
    first = eng._sample_one(req, logits_last[0]) if sample_first else None
    out.value = (primed, first, prompt.shape[1])


@sp_task(write=("state", "out"), name="serve.install")
def _install_codelet(state, out, *, eng, req, slot):
    # take the prefill's output and empty its cell: the graph keeps every
    # task and its cells, so a filled cell would pin one slot's worth of
    # KV cache in device memory for each admission the engine ever made
    (primed, first, n_fed), out.value = out.value, None
    st = state.value
    if first is not None:
        req.out_tokens.append(first)
        req.pending_tok = first
        req.t_first = time.perf_counter()
        req.t_tokens.append(req.t_first)
        eng._emit_token(req, first)
    caches, tok = eng._install(
        st["caches"], primed, st["tok"], jnp.int32(slot), jnp.int32(req.pending_tok)
    )
    eng._pos[slot] = n_fed
    eng._slot_req[slot] = req
    state.value = {"caches": caches, "tok": tok}
    eng._caches = caches
    eng._last_tok = tok
    if eng._spec is not None and req.speculative:
        eng._spec.prime_slot(slot, req)


@sp_task(write=("state",), name="serve.restore")
def _restore_codelet(state, *, eng, req, slot, rows, n_rows):
    """Prefix-cache hit / resume: scatter saved KV rows into the slot and
    join the decode batch with no prefill at all."""
    st = state.value
    caches = insert_cache_rows(st["caches"], slot, rows, 0)
    tok = st["tok"].at[slot, 0].set(req.pending_tok)
    eng._pos[slot] = n_rows
    eng._slot_req[slot] = req
    state.value = {"caches": caches, "tok": tok}
    eng._caches = caches
    eng._last_tok = tok
    if eng._spec is not None and req.speculative:
        eng._spec.prime_slot(slot, req)


class ServeEngine:
    """Continuously-batched decoding server over a paged KV cache.

    Context manager: ``with ServeEngine(cfg, params) as eng: ...`` stops the
    owned compute engine on exit even if the body raises.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        *,
        n_slots: int = 4,
        max_seq: int = 128,
        block_size: int = 8,
        n_blocks: Optional[int] = None,
        max_queue: int = 64,
        overload: str = "reject",
        max_batch: Optional[int] = None,
        admit_max_wait: float = 0.0,
        draft_cfg=None,
        draft_params=None,
        draft_k: int = 4,
        engine: Optional[SpComputeEngine] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        if n_blocks is None:
            n_blocks = n_slots * math.ceil(max_seq / block_size)
        self.pool = KVPagePool(n_blocks, block_size)
        self.scheduler = ServeScheduler(
            self.pool, n_slots, max_queue=max_queue, overload=overload,
            max_batch=max_batch, admit_max_wait=admit_max_wait,
            draft_k=draft_k if draft_cfg is not None else 0,
        )
        self._layout = cache_layout(cfg)
        self._pageable = self._layout is not None
        self._slot_req: dict[int, Request] = {}
        self._pos = np.zeros(n_slots, np.int32)
        self._caches = init_cache(cfg, n_slots, max_seq)
        self._last_tok = jnp.zeros((n_slots, 1), jnp.int32)
        self._own_engine = engine is None
        self.engine = engine or SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(2))
        self._force_rollback = 0
        self.stream_errors = 0
        self.steps = 0
        self.prefills = 0
        self.restores = 0
        self.cancels = 0
        self.closed = False

        self._decode, self._prefill = _jitted_steps(cfg)
        self._prefill_prime, self._install = _jitted_serve_ops(cfg, max_seq)
        self._sample_jit = _SAMPLE_JIT
        # ONE persistent graph for the engine's lifetime; every iteration
        # chains its codelets onto the same batch-state cell.  With a draft
        # model the graph runs under SP_MODEL_2 so speculation rounds
        # (spec.py) flow through the uncertain-writer chain machinery; the
        # plain decode path is unaffected (its certain writes clear any
        # uncertainty immediately).
        spec_model = (
            SpSpeculativeModel.SP_MODEL_2 if draft_cfg is not None
            else SpSpeculativeModel.SP_NO_SPEC
        )
        self._tg = SpTaskGraph(spec_model, trace=False).compute_on(self.engine)
        self._state = SpData(
            {"caches": self._caches, "tok": self._last_tok}, "serve_state"
        )
        self._spec = None
        if draft_cfg is not None:
            from repro.serving.spec import SpecDecoder

            self._spec = SpecDecoder(self, draft_cfg, draft_params, k=draft_k)

    # ------------------------------------------------------------------ API

    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int = 16,
        *,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        deadline: Optional[float] = None,
        speculative: Optional[bool] = None,
        on_token: Optional[callable] = None,
    ) -> Request:
        """Enqueue a request (thread-safe).  Raises AdmissionError when the
        bounded queue is full under the ``"reject"`` overload policy.
        ``deadline`` is *relative* seconds from now; past it the request is
        shed (queued) or cancelled with its KV blocks freed (running).

        ``speculative`` opts the request in/out of draft-model speculative
        decoding; the default (None) opts in iff the engine has a draft
        model.  Speculative and plain requests share one decode batch.
        ``on_token`` is invoked with each *committed* token as it lands
        (engine thread — it must be fast and must not raise; exceptions are
        swallowed and counted in ``stream_errors``)."""
        if self.closed:
            raise RuntimeError("ServeEngine is closed")
        if speculative is None:
            speculative = self._spec is not None
        elif speculative and self._spec is None:
            raise ValueError(
                "speculative=True needs an engine with a draft model "
                "(ServeEngine(draft_cfg=, draft_params=))"
            )
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) + max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq ({self.max_seq})"
            )
        now = time.perf_counter()
        req = Request(
            prompt,
            max_new_tokens,
            temperature=float(temperature),
            top_k=int(top_k),
            seed=int(seed),
            deadline=None if deadline is None else now + float(deadline),
            speculative=bool(speculative),
            on_token=on_token,
        )
        req.t_arrival = now
        self.scheduler.submit(req)
        return req

    @property
    def n_running(self) -> int:
        return len(self._slot_req)

    def step(self, wait: bool = True) -> None:
        """One engine iteration: chain this iteration's codelets onto the
        persistent graph.  Decode/collect for the current batch go in first,
        then admissions — so a newly admitted request's prefill overlaps the
        in-flight decode and its KV installs right after collect.

        When any running request opted into speculation (and the scheduler's
        draft-depth policy allows it), the decode/collect pair is replaced by
        one speculation round — k chained ``spec.draft`` uncertain writers,
        one ``spec.verify`` speculated reader, one ``spec.commit`` — which
        advances speculative slots by up to k+1 committed tokens while plain
        slots ride along at one token per round.  Rounds force ``wait``:
        round planning reads slot state the previous round must have
        committed."""
        spec_round = False
        with graph_scope(self._tg):
            if self._slot_req:
                spec_slots = [
                    s for s, r in self._slot_req.items() if r.speculative
                ] if self._spec is not None else []
                k = 0
                if spec_slots:
                    k = self.scheduler.draft_depth(len(spec_slots))
                    if k <= 0:
                        self._spec.sheds += 1  # pool pressure: plain decode
                if spec_slots and k > 0:
                    self._spec.insert_round(spec_slots, k)
                    spec_round = True
                else:
                    _decode_codelet(self._state, eng=self)
                    _collect_codelet(self._state, eng=self)
            for adm in self.scheduler.plan(pageable=self._pageable):
                self._insert_admission(adm)
        if wait or spec_round:
            self._tg.wait_all_tasks()
        self.steps += 1

    def run_until_drained(self, max_iters: int = 1000) -> None:
        """Pump until queue and batch are empty.  This is a convenience loop,
        not a barrier: submissions made while it runs are admitted mid-flight."""
        it = 0
        while (self.scheduler.queue_depth or self._slot_req) and it < max_iters:
            self.step()
            it += 1
        if self.scheduler.queue_depth or self._slot_req:
            raise RuntimeError("serve loop did not drain")

    def stats(self) -> dict:
        out = {
            "steps": self.steps,
            "prefills": self.prefills,
            "restores": self.restores,
            "cancels": self.cancels,
            "running": self.n_running,
            "pageable": self._pageable,
            "stream_errors": self.stream_errors,
        }
        out.update(self.scheduler.stats())
        out["pool"] = self.pool.stats()
        if self._spec is not None:
            out["spec"] = self._spec.stats()
            out["spec"]["graph"] = dict(self._tg.spec_stats)
        return out

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        if self._own_engine:
            self.engine.stop()

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    # ----------------------------------------------------------------- inner

    def _insert_admission(self, adm: Admission) -> None:
        req, slot, mode = adm.req, adm.slot, adm.mode
        if mode == "restore":
            table = self.pool.table_of(req.req_id)
            payloads = [self.pool.block(b).payload for b in table.block_ids]
            rows = concat_cache_rows(payloads)
            if not req.out_tokens:
                # fresh request via prefix cache: rows cover prompt[:-1];
                # the final prompt token rides the normal decode step
                req.pending_tok = int(req.prompt[-1])
            _restore_codelet(
                self._state, eng=self, req=req, slot=slot,
                rows=rows, n_rows=table.n_tokens,
            )
            self.restores += 1
        else:
            out = SpData(None, f"prefill.{req.req_id}")
            _prefill_codelet(
                out, eng=self, req=req, sample_first=(mode == "prefill")
            )
            _install_codelet(self._state, out, eng=self, req=req, slot=slot)
            self.prefills += 1

    def _writeback(self, slot: int, req: Request) -> None:
        """Save the slot's computed KV rows into the block payloads so a
        later prefix hit / resume can restore instead of re-prefilling."""
        if not self._pageable:
            return
        table = self.pool.table_of(req.req_id)
        if table is None:
            return
        bs = self.pool.block_size
        for i, bid in enumerate(table.block_ids):
            blk = self.pool.block(bid)
            a = i * bs
            b = min(a + len(blk.tokens), table.n_tokens)
            if blk.payload is None or blk.refcount <= 1:
                blk.payload = extract_cache_rows(self._caches, slot, a, b)

    def _emit_token(self, req: Request, tok: int) -> None:
        """Fire the streaming callback for one committed token."""
        if req.on_token is None:
            return
        try:
            req.on_token(tok)
        except Exception:
            self.stream_errors += 1

    def force_rollback(self, n: int = 1) -> None:
        """Poison the next ``n`` speculation rounds: their draft chains
        write the state cell, so the machinery rolls the verify back and
        re-executes it as a plain decode.  Output is unchanged (that is the
        point of the commit/rollback protocol); used by tests and chaos
        schedules."""
        if self._spec is None:
            raise RuntimeError("engine has no draft model; nothing to roll back")
        self._force_rollback += int(n)

    def _finish(self, slot: int) -> None:
        req = self._slot_req.pop(slot)
        req.done = True
        self._writeback(slot, req)
        self.pool.release(req.req_id, keep_resident=True)
        self.scheduler.free_slot(slot)
        if self._spec is not None:
            self._spec.drop_slot(slot)

    def _cancel_slot(self, slot: int, *, reason: Optional[str]) -> None:
        """Evict a running sequence whose output is no longer wanted
        (user ``cancel()`` or expired deadline): its KV blocks are freed
        immediately — no resumable writeback, unreferenced blocks returned
        to the pool mid-decode — and the slot rejoins the free list."""
        req = self._slot_req.pop(slot)
        req.done = True
        if reason is not None:
            req.rejected = True
            req.reject_reason = reason
        self.pool.release(req.req_id, keep_resident=False)
        self.scheduler.free_slot(slot)
        self.cancels += 1
        if self._spec is not None:
            self._spec.drop_slot(slot)

    def _preempt(self, slot: int) -> None:
        """Evict a running sequence: save its KV rows, release its blocks
        (resumable), and requeue it at the head of the admission queue."""
        req = self._slot_req.pop(slot)
        self._writeback(slot, req)
        self.pool.release(req.req_id, keep_resident=True)
        self.scheduler.free_slot(slot)
        req.preemptions += 1
        self.scheduler.requeue(req)
        if self._spec is not None:
            self._spec.drop_slot(slot)

    def _preempt_for(self, needy_slot: int) -> bool:
        victim = self.scheduler.preemption_victim(self._slot_req, exclude=needy_slot)
        if victim is None:
            return False
        self._preempt(victim[0])
        return True

    # -------------------------------------------------------------- sampling

    def _sample_batch(self, logits: jax.Array) -> jax.Array:
        """Per-slot sampling: greedy unless the slot's request asks for
        temperature/top-k, each with its own seeded key folded by the
        *absolute sequence position* of the token being sampled — not the
        engine step — so a position re-decoded after a speculation rollback
        or a preemption resume resamples the identical token, and the
        multi-position verify step can reproduce future positions' draws."""
        reqs = self._slot_req
        if all(r.temperature <= 0.0 for r in reqs.values()):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        B = logits.shape[0]
        temps = np.zeros(B, np.float32)
        topks = np.zeros(B, np.int32)
        keys = np.zeros((B, 2), np.uint32)
        for slot, r in reqs.items():
            temps[slot] = r.temperature
            topks[slot] = r.top_k
            if r.temperature > 0.0:
                keys[slot] = np.asarray(jax.random.fold_in(
                    jax.random.PRNGKey(r.seed), len(r.prompt) + len(r.out_tokens)
                ))
        return self._sample_jit(
            logits, jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(keys)
        )

    def _sample_one(self, req: Request, logits: jax.Array) -> int:
        if req.temperature <= 0.0:
            return int(jnp.argmax(logits))
        key = jax.random.fold_in(
            jax.random.PRNGKey(req.seed), len(req.prompt) + len(req.out_tokens)
        )
        tok = self._sample_jit(
            logits[None, :],
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
            jnp.asarray(key, jnp.uint32)[None, :],
        )
        return int(tok[0])


def _sample_logits(logits, temps, topks, keys):
    """Batched sampling: temperature scaling + top-k filter + categorical,
    falling back to argmax where ``temps == 0``.  (B, V) -> (B,) int32."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(jnp.where(topks > 0, topks, V) - 1, 0, V - 1)
    thresh = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    masked = jnp.where(scaled >= thresh, scaled, -jnp.inf)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


_SAMPLE_JIT = jax.jit(_sample_logits)
