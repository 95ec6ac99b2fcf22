"""Serving stack: paged KV cache (blocks, prefix sharing, COW, deterministic
LRU — paper §4.3 adapted), admission control/backpressure, and the
continuous-batching ServeEngine — token correctness vs a sequential generate
loop, mid-decode admission, restore-instead-of-prefill, preemption, and
per-request sampling controls."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.timeout(180)

from repro.configs import reduced_config
from repro.models import decode_step, init_params, prefill
from repro.runtime.serve import prime_cache
from repro.serving import (
    AdmissionError,
    KVPagePool,
    PageError,
    Request,
    ServeEngine,
    ServeScheduler,
)


# ---------------------------------------------------------------------------
# pool: blocks, refcounts, sharing, COW, LRU
# ---------------------------------------------------------------------------

def test_pool_allocate_free_refcount():
    pool = KVPagePool(4, block_size=4)
    t = pool.allocate(1, list(range(6)))  # one full + one partial block
    assert t.n_tokens == 6 and len(t.block_ids) == 2
    assert all(pool.refcount(b) == 1 for b in t.block_ids)
    assert pool.n_live == 2 and pool.n_free == 2
    pool.release(1, keep_resident=False)
    assert pool.n_live == 0 and pool.table_of(1) is None


def test_pool_prefix_share_full_and_partial():
    pool = KVPagePool(8, block_size=4)
    toks = list(range(6))
    t1 = pool.allocate(1, toks)
    t2 = pool.allocate(2, toks)  # exact match: shares full AND partial
    assert t1.block_ids == t2.block_ids
    assert all(pool.refcount(b) == 2 for b in t1.block_ids)
    assert pool.shared_hits == 2
    t3 = pool.allocate(3, toks[:4])  # prefix: shares only the full block
    assert t3.block_ids == t1.block_ids[:1]
    assert pool.refcount(t1.block_ids[0]) == 3


def test_pool_cow_on_shared_partial_append():
    pool = KVPagePool(8, block_size=4)
    toks = list(range(6))
    t1 = pool.allocate(1, toks)
    t2 = pool.allocate(2, toks)
    ev = pool.append_token(1, 99)  # divergent write into shared partial
    assert ev["cow"] is not None
    old, new = ev["cow"]
    assert t1.block_ids[-1] == new and t2.block_ids[-1] == old
    assert pool.refcount(old) == 1 and pool.refcount(new) == 1
    assert pool.block(new).tokens == [4, 5, 99]
    assert pool.block(old).tokens == [4, 5]
    assert pool.cow_copies == 1


def test_pool_deterministic_lru_eviction():
    pool = KVPagePool(2, block_size=4)
    t1 = pool.allocate(1, [1, 2, 3])
    pool.release(1, keep_resident=True)
    t2 = pool.allocate(2, [4, 5, 6])
    pool.release(2, keep_resident=True)
    # both evictable; seq 1's block has the older use stamp → evicted first
    pool.allocate(3, list(range(10, 15)))  # needs 2 blocks
    assert pool.evictions == 2
    assert not pool.resident(1) and not pool.resident(2)
    with pytest.raises(KeyError):
        pool.block(t1.block_ids[0])
    with pytest.raises(KeyError):
        pool.block(t2.block_ids[0])


def test_pool_resume_after_eviction_fails():
    pool = KVPagePool(2, block_size=4)
    pool.allocate(1, [1, 2, 3])
    pool.release(1, keep_resident=True)
    assert pool.resident(1)
    pool.allocate(2, list(range(10, 18)))  # evicts seq 1's block
    assert pool.resume(1) is None  # caller must re-prefill


def test_pool_resume_repins_blocks():
    pool = KVPagePool(4, block_size=4)
    t = pool.allocate(1, [1, 2, 3])
    pool.release(1, keep_resident=True)
    assert pool.refcount(t.block_ids[0]) == 0
    t2 = pool.resume(1)
    assert t2 is t and pool.refcount(t.block_ids[0]) == 1


def test_pool_allocate_rollback_is_atomic():
    pool = KVPagePool(2, block_size=4)
    t1 = pool.allocate(1, list(range(8)))  # pins both blocks
    with pytest.raises(PageError):
        pool.allocate(2, list(range(100, 108)))
    # failed allocation left nothing behind
    assert pool.table_of(2) is None
    assert pool.n_live == 2
    assert all(pool.refcount(b) == 1 for b in t1.block_ids)


def test_pool_page_error_when_all_pinned():
    pool = KVPagePool(1, block_size=4)
    pool.allocate(1, [1, 2, 3, 4])
    with pytest.raises(PageError):
        pool.append_token(1, 5)  # needs a second block; only one, pinned


# ---------------------------------------------------------------------------
# scheduler: bounded admission, overload policies, backpressure
# ---------------------------------------------------------------------------

def _req(prompt_len=5, seed=0):
    rng = np.random.default_rng(seed)
    return Request(rng.integers(0, 64, size=prompt_len).astype(np.int32))


def test_scheduler_reject_policy():
    sched = ServeScheduler(KVPagePool(8, 4), n_slots=2, max_queue=2)
    sched.submit(_req(seed=1))
    sched.submit(_req(seed=2))
    with pytest.raises(AdmissionError):
        sched.submit(_req(seed=3))
    assert sched.rejected == 1 and sched.queue_depth == 2


def test_scheduler_shed_oldest_policy():
    sched = ServeScheduler(
        KVPagePool(8, 4), n_slots=2, max_queue=2, overload="shed-oldest"
    )
    old = _req(seed=1)
    sched.submit(old)
    sched.submit(_req(seed=2))
    sched.submit(_req(seed=3))  # sheds `old`
    assert old.rejected and old.done and sched.shed == 1
    assert sched.queue_depth == 2


def test_scheduler_backpressure_when_pool_full():
    pool = KVPagePool(1, block_size=4)
    sched = ServeScheduler(pool, n_slots=2, max_queue=8)
    sched.submit(_req(prompt_len=8, seed=1))  # needs 2 blocks; pool has 1
    assert sched.plan(pageable=True) == []
    assert sched.queue_depth == 1  # stays queued, not dropped


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = reduced_config("deepseek-7b").replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _sequential_generate(cfg, params, prompt: np.ndarray, n: int, max_seq: int):
    """Oracle: prefill + single-sequence greedy decode loop."""
    logits, caches = prefill(params, {"tokens": jnp.asarray(prompt[None, :])}, cfg)
    caches = prime_cache(cfg, caches, len(prompt), max_seq)
    toks = [int(jnp.argmax(logits[0, -1]))]
    for s in range(n - 1):
        t = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, caches = decode_step(params, t, caches, jnp.int32(len(prompt) + s), cfg)
        toks.append(int(jnp.argmax(logits[0, 0])))
    return toks


def test_serve_engine_matches_sequential(served):
    cfg, params = served
    rng = np.random.default_rng(0)
    # staggered prompt lengths → per-slot positions differ
    prompts = [rng.integers(0, cfg.vocab, size=l).astype(np.int32) for l in (5, 9, 7)]
    N = 6
    with ServeEngine(cfg, params, n_slots=4, max_seq=32, block_size=4) as eng:
        reqs = [eng.submit(p, max_new_tokens=N) for p in prompts]
        eng.run_until_drained(max_iters=50)
        for p, r in zip(prompts, reqs):
            want = _sequential_generate(cfg, params, p, N, 32)
            assert r.done
            assert r.out_tokens == want, (r.out_tokens, want)


def test_serve_engine_admits_mid_decode(served):
    """Regression (continuous batching): a request arriving while another is
    mid-decode gets its prefill + first token immediately — it does not wait
    for in-flight sequences to drain."""
    cfg, params = served
    rng = np.random.default_rng(3)
    pa = rng.integers(0, cfg.vocab, size=9).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    with ServeEngine(cfg, params, n_slots=4, max_seq=32, block_size=4) as eng:
        A = eng.submit(pa, 12)
        for _ in range(3):
            eng.step()
        assert not A.done and eng.n_running == 1
        B = eng.submit(pb, 2)
        eng.step()  # B's prefill rides this step, concurrent with A's decode
        assert B.t_first is not None and not A.done
        eng.run_until_drained()
        assert B.done and A.done
        # B (2 tokens) finished strictly before A's last token
        assert B.t_tokens[-1] < A.t_tokens[-1]


def test_serve_engine_shared_prefix_refcount_and_cow(served):
    """Two requests with the same prompt share KV blocks (refcount == 2)
    until the first divergent write, which copy-on-writes the shared tail."""
    cfg, params = served
    rng = np.random.default_rng(4)
    p = rng.integers(0, cfg.vocab, size=9).astype(np.int32)
    with ServeEngine(cfg, params, n_slots=4, max_seq=32, block_size=4) as eng:
        a = eng.submit(p, 4)
        b = eng.submit(p, 4)
        eng.step()  # admission only: both prefilled + installed
        ta, tb = eng.pool.table_of(a.req_id), eng.pool.table_of(b.req_id)
        assert ta.block_ids == tb.block_ids
        assert [eng.pool.refcount(i) for i in ta.block_ids] == [2, 2, 2]
        eng.step()  # first appended token diverges the shared partial block
        assert eng.pool.cow_copies == 1
        assert ta.block_ids[-1] != tb.block_ids[-1]
        eng.run_until_drained()
        assert a.out_tokens == b.out_tokens  # greedy: same prompt, same text


def test_serve_engine_restore_skips_prefill(served):
    """A repeat prompt whose prefix blocks carry saved KV rows is admitted
    through restore — no prefill — and decodes identical tokens."""
    cfg, params = served
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab, size=9).astype(np.int32)  # 9 ≡ 1 (mod 4)
    with ServeEngine(cfg, params, n_slots=2, max_seq=32, block_size=4) as eng:
        r1 = eng.submit(p, 5)
        eng.run_until_drained()
        prefills = eng.prefills
        r2 = eng.submit(p, 5)
        eng.run_until_drained()
        assert eng.prefills == prefills  # no new prefill
        assert eng.restores == 1
        assert r2.out_tokens == r1.out_tokens


def test_serve_engine_evict_then_resume_reprefills(served):
    """Once a finished sequence's blocks are evicted by later traffic, a
    repeat prompt goes back through prefill (payloads are gone) and still
    produces the same tokens."""
    cfg, params = served
    rng = np.random.default_rng(6)
    p = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    with ServeEngine(cfg, params, n_slots=2, max_seq=16, block_size=4, n_blocks=4) as eng:
        r1 = eng.submit(p, 3)
        eng.run_until_drained()
        for seed in (7, 8):  # distinct traffic evicts p's resident blocks
            eng.submit(rng.integers(0, cfg.vocab, size=5).astype(np.int32), 3)
            eng.run_until_drained()
        assert eng.pool.evictions >= 1
        prefills = eng.prefills
        r2 = eng.submit(p, 3)
        eng.run_until_drained()
        assert eng.prefills == prefills + 1 and eng.restores == 0
        assert r2.out_tokens == r1.out_tokens


def test_serve_engine_preemption_roundtrip(served):
    """Under a pool too small for both sequences, one is preempted mid-decode
    (written back + requeued) and both still finish with exactly the tokens
    an unpressured run produces."""
    cfg, params = served
    rng = np.random.default_rng(7)
    p1 = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    with ServeEngine(cfg, params, n_slots=2, max_seq=16, block_size=4, n_blocks=4) as eng:
        r1, r2 = eng.submit(p1, 8), eng.submit(p2, 8)
        eng.run_until_drained(max_iters=200)
        assert r1.done and r2.done
        assert eng.scheduler.preemptions >= 1
    with ServeEngine(cfg, params, n_slots=2, max_seq=16, block_size=4) as eng:
        q1, q2 = eng.submit(p1, 8), eng.submit(p2, 8)
        eng.run_until_drained()
        assert q1.out_tokens == r1.out_tokens
        assert q2.out_tokens == r2.out_tokens


def test_serve_engine_admission_reject_and_occupancy(served):
    cfg, params = served
    rng = np.random.default_rng(8)
    mk = lambda: rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    with ServeEngine(cfg, params, n_slots=2, max_seq=16, block_size=4,
                     max_queue=1) as eng:
        eng.submit(mk(), 2)
        with pytest.raises(AdmissionError):
            eng.submit(mk(), 2)  # bounded queue full before any step
        assert eng.stats()["rejected"] == 1
        eng.step()
        assert eng.scheduler.slot_occupancy == 0.5
        eng.run_until_drained()


def test_serve_engine_sampling_deterministic(served):
    cfg, params = served
    rng = np.random.default_rng(9)
    p = rng.integers(0, cfg.vocab, size=7).astype(np.int32)

    def run(temp, top_k, seed):
        with ServeEngine(cfg, params, n_slots=2, max_seq=32, block_size=4) as eng:
            r = eng.submit(p, 5, temperature=temp, top_k=top_k, seed=seed)
            eng.run_until_drained()
            return r.out_tokens

    assert run(0.8, 5, 42) == run(0.8, 5, 42)  # same seed → same tokens
    assert run(1.0, 1, 3) == run(0.0, 0, 0)  # top-1 sampling ≡ greedy


def test_serve_engine_context_manager_closes(served):
    cfg, params = served
    rng = np.random.default_rng(10)
    p = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    with ServeEngine(cfg, params, n_slots=2, max_seq=16, block_size=4) as eng:
        r = eng.submit(p, 2)
        eng.run_until_drained()
        assert r.done
    assert eng.closed
    with pytest.raises(RuntimeError):
        eng.submit(p, 2)


def test_serve_engine_device_memory_flat_across_waves(served):
    """Regression: the engine's persistent graph keeps every task and its
    cells, so a cell left holding a prefill's KV output pinned device memory
    for every admission the engine ever made; at deepseek-7b widths the
    engine ran out of chip memory in its second wave.  Live device bytes
    after a wave must not grow wave over wave."""
    import gc

    cfg, params = served
    rng = np.random.default_rng(11)
    live = []
    with ServeEngine(cfg, params, n_slots=2, max_seq=32, block_size=4) as eng:
        for _ in range(3):
            for _ in range(3):
                eng.submit(rng.integers(0, cfg.vocab, size=6).astype(np.int32), 5)
            eng.run_until_drained()
            gc.collect()
            live.append(sum(a.nbytes for a in jax.live_arrays()))
    assert live[2] == live[1], live
