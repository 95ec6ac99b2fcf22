"""KV writeback (``ServeEngine._writeback``): a finished or preempted
sequence's rows reach the host in fixed ``WRITEBACK_CHUNK``-row gathers,
one host copy per chunk.  The payloads must equal, bit for bit, the
per-block rows that ``extract_cache_rows`` cuts; shared blocks that already
hold a payload are skipped; the gather compiles once whatever the lengths;
and the call leaves no device array behind."""
from __future__ import annotations

import gc
import itertools

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import init_params
from repro.runtime.serve import extract_cache_rows
from repro.serving import ServeEngine
from repro.serving import engine as engine_mod

pytestmark = pytest.mark.timeout(300)

C = engine_mod.WRITEBACK_CHUNK
MAX_SEQ = 2 * C + 40  # the last chunk runs past max_seq: its start is clamped
_ids = itertools.count(10**6)


@pytest.fixture(scope="module")
def served():
    # dense attention at every prompt length: a resumed sequence re-prefills
    # a length off the blockwise path's grid
    cfg = reduced_config("deepseek-7b").replace(attn_blockwise_min_seq=MAX_SEQ)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


class _Seq:
    """The one field of a request that ``_writeback`` reads."""

    def __init__(self):
        self.req_id = next(_ids)


def _engine(cfg, params, block_size, max_seq=MAX_SEQ, **kw):
    """An engine whose cache holds distinct random rows in every slot."""
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=max_seq, block_size=block_size, **kw)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    eng._caches = jax.tree.map(
        lambda c: jax.random.normal(next(keys), c.shape).astype(c.dtype), eng._caches
    )
    return eng


def _same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert isinstance(g, np.ndarray) and g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g.view(np.uint16), w.view(np.uint16))


def _check_payloads(eng, slot, table, blocks=None):
    bs = eng.pool.block_size
    for i, bid in enumerate(table.block_ids):
        if blocks is not None and bid not in blocks:
            continue
        blk = eng.pool.block(bid)
        a = i * bs
        b = min(a + len(blk.tokens), table.n_tokens)
        _same(blk.payload, extract_cache_rows(eng._caches, slot, a, b))


def _spans(a, b, rows):
    return set(range(a // rows, (b - 1) // rows + 1))


def _count_gathers(eng):
    calls = []
    real = eng._gather_rows

    def spy(caches, slot, start):
        calls.append((int(slot), int(start)))
        return real(caches, slot, start)

    eng._gather_rows = spy
    return calls


@pytest.mark.parametrize("block_size", [16, 24])  # 24 does not divide C: blocks straddle chunks
@pytest.mark.parametrize("n", [1, 15, 16, 17, C - 1, C, C + 1, MAX_SEQ])
def test_payloads_equal_per_block_rows(served, block_size, n):
    cfg, params = served
    rng = np.random.default_rng(n)
    with _engine(cfg, params, block_size) as eng:
        calls = _count_gathers(eng)
        seq = _Seq()
        table = eng.pool.allocate(seq.req_id, rng.integers(0, cfg.vocab, size=n).tolist())
        eng._writeback(1, seq)
        _check_payloads(eng, 1, table)
        bs = block_size
        want = set().union(*(_spans(i * bs, min(i * bs + bs, n), C)
                             for i in range(len(table.block_ids))))
        assert sorted(start for _, start in calls) == sorted(min(k * C, MAX_SEQ - C) for k in want)
        assert {slot for slot, _ in calls} == {1}


def test_shared_blocks_with_a_payload_are_skipped(served):
    cfg, params = served
    bs = 16
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab, size=2 * C).tolist()
    with _engine(cfg, params, bs) as eng:
        first, second = _Seq(), _Seq()
        eng.pool.allocate(first.req_id, prefix)
        table = eng.pool.allocate(second.req_id, prefix + [1, 2, 3])
        shared = table.block_ids[: 2 * C // bs]
        assert all(eng.pool.refcount(b) == 2 for b in shared)
        saved = object()
        for b in shared[1:]:  # the first shared block has no payload yet
            eng.pool.block(b).payload = saved
        calls = _count_gathers(eng)
        eng._writeback(0, second)
        assert all(eng.pool.block(b).payload is saved for b in shared[1:])
        # only the first block and the private tail are copied: chunks 0 and 2
        assert [start for _, start in calls] == [0, MAX_SEQ - C]
        _check_payloads(eng, 0, table, blocks={shared[0], *table.block_ids[2 * C // bs:]})


def test_one_gather_program_for_every_length(served):
    cfg, params = served
    rng = np.random.default_rng(2)
    with _engine(cfg, params, 16) as eng:
        for slot, n in [(0, 3), (1, C + 7), (0, MAX_SEQ), (1, 2 * C - 1)]:
            seq = _Seq()
            eng.pool.allocate(seq.req_id, rng.integers(0, cfg.vocab, size=n).tolist())
            eng._writeback(slot, seq)
            eng.pool.release(seq.req_id, keep_resident=False)
        assert eng._gather_rows._cache_size() == 1


def test_no_device_array_outlives_a_writeback(served):
    cfg, params = served
    rng = np.random.default_rng(3)
    with _engine(cfg, params, 16) as eng:
        seq = _Seq()
        table = eng.pool.allocate(seq.req_id, rng.integers(0, cfg.vocab, size=2 * C + 9).tolist())
        eng._writeback(0, _Seq())  # nothing allocated for it: returns at once
        warm = _Seq()
        eng.pool.allocate(warm.req_id, [1])
        eng._writeback(0, warm)  # compile outside the count
        gc.collect()
        before = len(jax.live_arrays())
        eng._writeback(1, seq)
        gc.collect()
        assert len(jax.live_arrays()) == before
        _check_payloads(eng, 1, table)


def _record_writebacks(eng):
    """Wrap ``_writeback`` to check, on every call, that each payload it
    wrote equals the per-block rows of the slot; return the list of
    (finished, rows) of the calls."""
    seen = []
    real = eng._writeback

    def checked(slot, req):
        table = eng.pool.table_of(req.req_id)
        old = {b: eng.pool.block(b).payload for b in table.block_ids}
        real(slot, req)
        written = {b for b in table.block_ids if eng.pool.block(b).payload is not old[b]}
        _check_payloads(eng, slot, table, blocks=written)
        seen.append((req.done, table.n_tokens))

    eng._writeback = checked
    return seen


def test_finish_writes_back_every_chunk_and_restores(served):
    """A request longer than one chunk, with its last chunk clamped,
    finishes with host payloads that restore it without a prefill."""
    cfg, params = served
    rng = np.random.default_rng(4)
    # a restore covers prompt[:-1], here 17 full blocks
    p = rng.integers(0, cfg.vocab, size=C + 17).astype(np.int32)
    with ServeEngine(cfg, params, n_slots=2, max_seq=C + 40, block_size=16) as eng:
        seen = _record_writebacks(eng)
        r1 = eng.submit(p, 6)
        eng.run_until_drained()
        assert seen == [(True, C + 17 + 5)]
        prefills = eng.prefills
        r2 = eng.submit(p, 6)
        eng.run_until_drained()
        assert eng.prefills == prefills and eng.restores == 1
        assert r2.out_tokens == r1.out_tokens


def test_preemption_writes_back_every_chunk(served):
    """Two sequences of 17 blocks each, in a pool of 35: the one that needs
    an 18th block second preempts the other past the first chunk's end,
    which resumes, and both finish with the tokens of an unpressured run."""
    cfg, params = served
    rng = np.random.default_rng(5)
    p1, p2 = (rng.integers(0, cfg.vocab, size=C + 8).astype(np.int32) for _ in range(2))
    kw = dict(n_slots=2, max_seq=C + 40, block_size=16)
    with ServeEngine(cfg, params, n_blocks=2 * (C + 8) // 16 + 1, **kw) as eng:
        seen = _record_writebacks(eng)
        r1, r2 = eng.submit(p1, 24), eng.submit(p2, 24)
        eng.run_until_drained(max_iters=400)
        assert eng.scheduler.preemptions >= 1
        assert any(not done and rows > C for done, rows in seen), seen
    with ServeEngine(cfg, params, **kw) as eng:
        q1, q2 = eng.submit(p1, 24), eng.submit(p2, 24)
        eng.run_until_drained()
    assert (r1.out_tokens, r2.out_tokens) == (q1.out_tokens, q2.out_tokens)
