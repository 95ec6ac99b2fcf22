"""Speculative decoding on the runtime's commit/rollback speculation engine.

The paper's central mechanism (§4.6) is speculative task execution: a chain
of *uncertain writers* (``maybe``-write accesses) shares one snapshot under
``SP_MODEL_2``; a reader of the uncertain cell is rewritten into a
speculative body that runs on the snapshot plus a commit task that either
promotes the speculative result (no writer wrote) or re-executes the body
on the real value (rollback).  Draft-model speculative decoding maps onto
that machinery exactly — see the "Speculative decoding" section of
``core/speculation.py`` for the full mapping:

* ``spec.draft`` (×k) — one draft-model decode step per task, chained as
  ``maybe``-writers on the engine's batch-state cell.  In the normal case a
  draft never writes the state (drafted tokens are *proposals*, not state);
  when speculation must be abandoned mid-chain (pool pressure shed, forced
  rollback) the draft *does* write, poisoning the chain.
* ``spec.verify`` — reads the uncertain state cell, so the machinery turns
  it into a speculative body + commit task.  The body runs ONE multi-
  position target forward (``models.verify_step``) over the k drafted
  positions plus the pending token, samples the target's token at every
  position, and accepts the longest matching draft prefix plus one bonus
  token.  The body is pure with respect to engine state because the
  machinery may run it twice: speculatively, and again on rollback (where
  it sees ``round.abort`` and degrades to a plain one-token decode).
* ``spec.commit`` — a *certain* write on the state cell: installs the
  advanced state (tearing down the uncertainty chain for the next round)
  and performs every externally visible effect exactly once — pool block
  appends, ``out_tokens``, streaming callbacks, staged-payload promotion.

Greedy verification is bit-exact with non-speculative decode: the verify
forward's per-position math is literally ``decode_step`` unrolled, so the
target tokens it samples are the tokens the plain engine would have
produced, and only target-sampled tokens are ever committed.  The same
argument covers temperature sampling because sampling keys are folded by
absolute sequence position (not engine step), so position ``p`` samples
identically no matter how many draft rounds, rollbacks, or preemptions
preceded it.

Draft KV state: the draft model keeps its own dense cache per slot,
self-healed across rounds — rows written for rejected drafts sit beyond
the committed cursor, where the causal mask hides them until the row is
overwritten by the next feed at that position.  This is also why both the
target and the draft must be families with per-token KV rows
(``cache_layout(cfg) is not None``): a recurrent state cannot rewind.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SpData, sp_task
from repro.models import cache_layout, init_cache
from repro.runtime.serve import build_verify_fn, extract_cache_rows
from repro.serving.kvcache import PageError


def shrunken_draft(cfg, params=None, *, n_layers: int = 1):
    """Default draft preset: the target config truncated to its first
    ``n_layers`` layers (same vocab, same family, same cache geometry).
    With ``params`` given, the draft reuses the target's embedding/head and
    the leading layer stack — a free low-quality draft; without, the caller
    fits or initializes the draft itself.  → (draft_cfg, draft_params)."""
    draft_cfg = cfg.replace(n_layers=n_layers)
    if cache_layout(draft_cfg) is None:
        raise ValueError(
            f"family {cfg.family!r} has no per-token KV rows to rewind; "
            "speculative drafting needs cache_layout(cfg) is not None"
        )
    draft_params = None
    if params is not None:
        draft_params = dict(params)
        draft_params["layers"] = jax.tree.map(
            lambda t: t[:n_layers], params["layers"]
        )
    return draft_cfg, draft_params


@dataclass
class _RoundSlot:
    """Per-slot drafting state for one speculation round."""

    P: int                    # verify anchor: eng._pos[slot] at round start
    queue: list               # committed-but-unfed draft tokens, pending last
    dp: int                   # next draft-cache feed position
    proposals: list = field(default_factory=list)
    last_tok: int = 0
    fed_log: list = field(default_factory=list)  # [(pos, tok)] feeds performed


@dataclass
class SpecRound:
    """One speculation round: k draft feeds chained as uncertain writers,
    one verify, one commit.  ``abort`` flips when a draft poisons the chain
    (shed / forced rollback) — the machinery then rolls the verify back."""

    k: int
    per_slot: dict = field(default_factory=dict)  # slot -> _RoundSlot
    n_feeds: int = 0
    abort: bool = False

    @property
    def slots(self):
        return self.per_slot


# ---------------------------------------------------------------------------
# Codelets (``eng``/``rnd`` are static parameters; data slots carry the
# engine's batch-state cell plus two per-round cells).
# ---------------------------------------------------------------------------

@sp_task(maybe=("state",), write=("prop",), name="spec.draft", cost=2.0)
def _draft_codelet(state, prop, *, eng, rnd, j):
    """One draft-model decode feed.  ``maybe``-write on the batch state:
    normally it never assigns (drafts are proposals, committed only by
    ``spec.commit``); on shed/forced-rollback it poisons the chain so the
    machinery re-executes the verify on the real state."""
    if not rnd.abort and (
        eng._force_rollback > 0
        or eng.scheduler.draft_depth(len(rnd.per_slot)) <= 0
    ):
        rnd.abort = True
    if rnd.abort:
        state.value = state.value  # uncertain write -> machinery rollback
    else:
        eng._spec._draft_feed(rnd)
    prop.value = j


@sp_task(read=("state", "prop"), write=("vout",), name="spec.verify", cost=10.0)
def _verify_codelet(state, prop, vout, *, eng, rnd):
    """Speculated reader of the uncertain state cell.  Pure w.r.t. engine
    state — the machinery may run this body twice (speculatively, then on
    rollback); all effects live in ``spec.commit``."""
    vout.value = eng._spec._verify(rnd, state)


@sp_task(write=("state",), read=("vout",), name="spec.commit")
def _commit_codelet(state, vout, *, eng, rnd):
    """Certain write on the state cell: installs the advanced batch state
    (clearing the uncertainty chain) and applies all external effects."""
    eng._spec._commit(rnd, vout, state)


class SpecDecoder:
    """Draft-model speculative decoding bolted onto a :class:`ServeEngine`.

    Owns the draft model (config/params/jitted steps), the per-slot draft
    KV cache, and the round lifecycle.  The engine consults it from
    ``step()`` when any running request opted into speculation.
    """

    def __init__(self, eng, draft_cfg, draft_params, k: int = 4):
        if k < 1:
            raise ValueError("draft depth k must be >= 1")
        if cache_layout(eng.cfg) is None:
            raise ValueError(
                "speculative decoding needs a pageable target family "
                "(cache_layout(cfg) is not None): stale KV rows beyond the "
                "accepted position must be maskable and overwritable"
            )
        if cache_layout(draft_cfg) is None:
            raise ValueError("draft family must have per-token KV rows too")
        if draft_cfg.vocab != eng.cfg.vocab:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab} != target vocab {eng.cfg.vocab}"
            )
        from repro.serving.engine import _jitted_serve_ops, _jitted_steps

        self.eng = eng
        self.cfg = draft_cfg
        self.params = draft_params
        self.k = int(k)
        self._decode, _ = _jitted_steps(draft_cfg)
        self._prime, self._install = _jitted_serve_ops(draft_cfg, eng.max_seq)
        self._caches = init_cache(draft_cfg, eng.n_slots, eng.max_seq)
        self._dummy_tok = jnp.zeros((eng.n_slots, 1), jnp.int32)
        # the verify forward must NOT donate the state caches: on rollback
        # the body re-runs against the same state value
        self._verify_jit = jax.jit(build_verify_fn(eng.cfg, jit=False))
        self._next_pos: dict[int, int] = {}  # slot -> draft rows valid below
        # slot -> (start, rows): committed verify rows carried across rounds
        # so blocks that straddle a round boundary can still be promoted
        self._staged_tail: dict[int, tuple] = {}
        self.rounds = 0
        self.rollback_rounds = 0
        self.sheds = 0
        self.proposed = 0
        self.accepted = 0
        self.committed_tokens = 0
        self.draft_feeds = 0
        self.staged_promotions = 0

    # -------------------------------------------------------------- lifecycle

    def prime_slot(self, slot: int, req) -> None:
        """Build the draft model's KV rows for everything the target has
        already fed in this slot (admission, restore, preemption resume).
        The draft prefill is one cheap call — the draft is small by
        construction."""
        n = int(self.eng._pos[slot])
        if n >= 1:
            full = [int(t) for t in req.prompt] + [int(t) for t in req.out_tokens]
            toks = np.asarray(full[:n], np.int32)[None, :]
            _, primed, _ = self._prime(self.params, {"tokens": jnp.asarray(toks)})
            self._caches, _ = self._install(
                self._caches, primed, self._dummy_tok, jnp.int32(slot), jnp.int32(0)
            )
        self._next_pos[slot] = n

    def drop_slot(self, slot: int) -> None:
        self._next_pos.pop(slot, None)
        self._staged_tail.pop(slot, None)

    def insert_round(self, spec_slots: list, k: int) -> SpecRound:
        """Chain one round's draft/verify/commit codelets onto the engine's
        graph (caller holds ``graph_scope``)."""
        eng = self.eng
        rnd = SpecRound(k=k)
        for slot in spec_slots:
            req = eng._slot_req[slot]
            full = [int(t) for t in req.prompt] + [int(t) for t in req.out_tokens]
            P = int(eng._pos[slot])
            npos = min(self._next_pos.get(slot, 0), P)
            rnd.per_slot[slot] = _RoundSlot(P=P, queue=full[npos:P + 1], dp=npos)
        rnd.n_feeds = max(len(s.queue) - 1 for s in rnd.per_slot.values()) + k
        prop = SpData(None, f"spec.prop.{eng.steps}")
        vout = SpData(None, f"spec.vout.{eng.steps}")
        for j in range(rnd.n_feeds):
            _draft_codelet(eng._state, prop, eng=eng, rnd=rnd, j=j)
        _verify_codelet(eng._state, prop, vout, eng=eng, rnd=rnd)
        _commit_codelet(eng._state, vout, eng=eng, rnd=rnd)
        return rnd

    # --------------------------------------------------------------- drafting

    def _draft_feed(self, rnd: SpecRound) -> None:
        """One batched draft decode step.  Each spec slot feeds its next
        token — catch-up (committed but not yet in the draft cache), the
        pending token, or its own last proposal — at its own position; a
        slot already holding k proposals re-feeds its last token at the
        same position (an idempotent KV row rewrite)."""
        eng = self.eng
        B = eng.n_slots
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        gen = {}
        for slot, s in rnd.per_slot.items():
            if s.queue:
                t = s.queue.pop(0)
                p, s.dp = s.dp, s.dp + 1
                gen[slot] = not s.queue and len(s.proposals) < rnd.k
                s.fed_log.append((p, t))
            elif len(s.proposals) < rnd.k:
                t = s.proposals[-1]
                p, s.dp = s.dp, s.dp + 1
                gen[slot] = True
                s.fed_log.append((p, t))
            else:
                t, p = s.last_tok, s.dp - 1  # idempotent re-feed
                gen[slot] = False
            s.last_tok = t
            toks[slot, 0] = t
            pos[slot] = min(p, eng.max_seq - 1)
        logits, self._caches, _ = self._decode(
            self.params, jnp.asarray(toks), self._caches, jnp.asarray(pos)
        )
        self.draft_feeds += 1
        arg = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for slot, s in rnd.per_slot.items():
            if gen[slot]:
                s.proposals.append(int(arg[slot]))

    # ------------------------------------------------------------ verify body

    def _verify(self, rnd: SpecRound, st) -> dict:
        """One batched multi-position target forward + per-position target
        sampling + acceptance.  Pure w.r.t. engine state: may run twice
        (speculative body, then rollback re-execution)."""
        eng = self.eng
        B = eng.n_slots
        tok0 = np.asarray(st["tok"]).copy()
        if rnd.abort:
            T = 1
            toks = tok0
            adv = np.zeros(B, np.int32)
        else:
            T = rnd.k + 1
            toks = np.repeat(tok0, T, axis=1)
            adv = np.zeros(B, np.int32)
            for slot, s in rnd.per_slot.items():
                adv[slot] = 1
                for j, d in enumerate(s.proposals):
                    toks[slot, 1 + j] = d
        pos = np.asarray(eng._pos, np.int32)
        logits, new_caches = self._verify_jit(
            eng.params, jnp.asarray(toks), st["caches"],
            jnp.asarray(pos), jnp.asarray(adv),
        )
        tgt = self._sample_positions(logits, pos, T)
        new_tok = tok0.copy()
        per = {}
        for slot, req in eng._slot_req.items():
            s = None if rnd.abort else rnd.per_slot.get(slot)
            if s is None:
                nxt = int(tgt[slot, 0])
                per[slot] = {
                    "fed": [int(tok0[slot, 0])], "out": [nxt], "accepted": 0,
                }
                new_tok[slot, 0] = nxt
                continue
            a = 0
            while a < rnd.k and int(tgt[slot, a]) == s.proposals[a]:
                a += 1
            out = [int(t) for t in tgt[slot, : a + 1]]
            per[slot] = {
                "fed": [int(tok0[slot, 0])] + s.proposals[:a],
                "out": out,
                "accepted": a,
            }
            new_tok[slot, 0] = out[-1]
            if eng._pageable:
                # the k+1 freshly computed target KV rows are *uncommitted*
                # until spec.commit promotes the accepted prefix
                stop = min(s.P + rnd.k + 1, eng.max_seq)
                rows = extract_cache_rows(new_caches, slot, s.P, stop)
                eng.pool.stage_rows(req.req_id, s.P, rows)
        return {
            "abort": rnd.abort,
            "state": {"caches": new_caches, "tok": jnp.asarray(new_tok)},
            "per": per,
        }

    def _sample_positions(self, logits, pos, T: int) -> np.ndarray:
        """Target tokens for every (slot, sub-step): greedy argmax, or the
        engine's sampler with keys folded by absolute sequence position —
        the same key the plain decode path would fold for that position."""
        eng = self.eng
        reqs = eng._slot_req
        if all(r.temperature <= 0.0 for r in reqs.values()):
            return np.asarray(jnp.argmax(logits, axis=-1))
        B = logits.shape[0]
        cols = []
        for t in range(T):
            temps = np.zeros(B, np.float32)
            topks = np.zeros(B, np.int32)
            keys = np.zeros((B, 2), np.uint32)
            for slot, r in reqs.items():
                temps[slot] = r.temperature
                topks[slot] = r.top_k
                if r.temperature > 0.0:
                    keys[slot] = np.asarray(jax.random.fold_in(
                        jax.random.PRNGKey(r.seed), int(pos[slot]) + t + 1
                    ))
            cols.append(np.asarray(eng._sample_jit(
                logits[:, t], jnp.asarray(temps), jnp.asarray(topks),
                jnp.asarray(keys),
            )))
        return np.stack(cols, axis=1)

    # ------------------------------------------------------------ commit body

    def _commit(self, rnd: SpecRound, v: dict, state) -> None:
        """All externally visible effects of the round, applied exactly
        once: install the advanced state (certain write → chain teardown),
        account fed tokens into the pool, append committed tokens, fire
        streaming callbacks, promote staged KV payloads, finish/cancel."""
        eng = self.eng
        self.rounds += 1
        if v["abort"]:
            self.rollback_rounds += 1
            if eng._force_rollback > 0:
                eng._force_rollback -= 1
        state.value = v["state"]
        eng._caches = v["state"]["caches"]
        eng._last_tok = v["state"]["tok"]
        now = time.perf_counter()
        for slot in sorted(eng._slot_req):
            req = eng._slot_req.get(slot)
            if req is None:  # preempted as a victim earlier in this loop
                continue
            if req.cancelled:
                eng.pool.drop_staged(req.req_id)
                eng._cancel_slot(slot, reason=None)
                continue
            if req.deadline is not None and now > req.deadline:
                eng.pool.drop_staged(req.req_id)
                eng._cancel_slot(slot, reason="deadline")
                continue
            info = v["per"][slot]
            s = rnd.per_slot.get(slot)
            if s is not None and not v["abort"]:
                self.proposed += rnd.k
                self.accepted += info["accepted"]
                req.spec_rounds += 1
                req.spec_accepted += info["accepted"]
            alive = True
            for ftok, ntok in zip(info["fed"], info["out"]):
                try:
                    eng.pool.append_token(req.req_id, ftok)
                except PageError:
                    if not eng._preempt_for(slot):
                        eng._preempt(slot)
                        alive = False
                        break
                    eng.pool.append_token(req.req_id, ftok)
                eng._pos[slot] += 1
                req.out_tokens.append(int(ntok))
                req.pending_tok = int(ntok)
                if req.t_first is None:
                    req.t_first = now
                req.t_tokens.append(now)
                eng._emit_token(req, int(ntok))
                self.committed_tokens += 1
                if (len(req.out_tokens) >= req.max_new_tokens
                        or eng._pos[slot] >= eng.max_seq):
                    self._promote_staged(slot, req)
                    eng._finish(slot)
                    alive = False
                    break
            if not alive:
                continue
            self._promote_staged(slot, req)
            if s is not None:
                self._advance_draft_cursor(slot, req, s)

    def _advance_draft_cursor(self, slot: int, req, s: _RoundSlot) -> None:
        """Draft rows are valid up to the first fed token that disagrees
        with the committed sequence (rejected proposals leave stale rows,
        self-healed by later overwrites)."""
        full = [int(t) for t in req.prompt] + [int(t) for t in req.out_tokens]
        cur = self._next_pos.get(slot, 0)
        for p, t in s.fed_log:
            if p < cur:
                continue  # idempotent re-feed of an already-valid row
            if p == cur and p < len(full) and full[p] == t:
                cur += 1
            else:
                break
        self._next_pos[slot] = cur

    def _promote_staged(self, slot: int, req) -> None:
        """Move accepted uncommitted KV rows into block payloads: any block
        that fills up with committed rows becomes payload-backed immediately
        (restorable without waiting for the finish-time writeback).  Rounds
        rarely align with block boundaries, so the committed trailing rows
        of each round are retained and merged into the next round's window
        — a straddling block still gets promoted once its last row lands."""
        eng = self.eng
        st = eng.pool.take_staged(req.req_id)
        if st is None or not eng._pageable:
            return
        start, rows = st
        n_rows = jax.tree.leaves(rows)[0].shape[1]
        # rows past the committed position came from rejected proposals:
        # their tokens are not what will occupy those positions
        end = min(start + n_rows, int(eng._pos[slot]))
        if end <= start:
            self._staged_tail.pop(slot, None)
            return
        tail = self._staged_tail.pop(slot, None)
        if tail is not None:
            t_start, t_rows = tail
            t_end = t_start + jax.tree.leaves(t_rows)[0].shape[1]
            if t_start < start <= t_end:  # contiguous: prepend retained rows
                keep = start - t_start
                rows = jax.tree.map(
                    lambda a, b: jnp.concatenate([a[:, :keep], b], axis=1),
                    t_rows, rows,
                )
                start = t_start
        table = eng.pool.table_of(req.req_id)
        if table is None:
            return
        bs = eng.pool.block_size
        for i, bid in enumerate(table.block_ids):
            blk = eng.pool.block(bid)
            a, b = i * bs, i * bs + len(blk.tokens)
            if (blk.full and blk.payload is None
                    and a >= start and b <= end):
                blk.payload = jax.tree.map(
                    lambda t: t[:, a - start:b - start], rows
                )
                self.staged_promotions += 1
        # carry the committed rows of the still-partial trailing block
        t_start = max(start, (end // bs) * bs)
        if t_start < end:
            self._staged_tail[slot] = (
                t_start,
                jax.tree.map(lambda t: t[:, t_start - start:end - start], rows),
            )

    # ------------------------------------------------------------------ stats

    def stats(self) -> dict:
        return {
            "draft_k": self.k,
            "rounds": self.rounds,
            "rollback_rounds": self.rollback_rounds,
            "sheds": self.sheds,
            "draft_feeds": self.draft_feeds,
            "proposed": self.proposed,
            "accepted": self.accepted,
            "accept_rate": self.accepted / max(self.proposed, 1),
            "committed_tokens": self.committed_tokens,
            "accepted_per_round": self.committed_tokens / max(self.rounds, 1),
            "staged_promotions": self.staged_promotions,
        }
