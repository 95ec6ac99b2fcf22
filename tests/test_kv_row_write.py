"""Decode writes each slot's new KV row into the cache stack in place.

The row write (one scatter per cache leaf at ``(layer, slot, pos[slot])``)
must leave exactly the caches and tokens of the one-hot select
``cache·(1−δ) + new·δ`` that a sequence-sharded cache still takes: in any
float format ``x·1 + y·0`` is ``x``, so the two agree bit for bit.  The
select is forced here by reporting every cache as sequence-sharded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import attention as attn_mod
from repro.models import decode_step, init_cache, init_params, verify_step
from repro.models.attention import kv_cache_update

pytestmark = pytest.mark.timeout(180)

B, S = 4, 16


def _filled_cache(cfg, key):
    """A cache whose every row already holds a distinct value, so a write to
    the wrong row, or a lost one, changes it."""
    caches = init_cache(cfg, B, S)
    leaves, tree = jax.tree.flatten(caches)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype) for k, x in zip(keys, leaves)
    ])


_CFGS = {
    "dense": lambda: reduced_config("deepseek-7b"),
    "mla": lambda: reduced_config("deepseek-v2-lite-ep8"),
    "window": lambda: reduced_config("deepseek-7b").replace(attn_window=S // 2),
    "hybrid": lambda: reduced_config("recurrentgemma-9b"),
}

#: (config, positions of the B slots at the first of three steps, verify)
CASES = {
    "per_slot_first_and_last": ("dense", [0, 5, S - 3, 9], None),
    "scalar_position": ("dense", 6, None),
    "mla_per_slot": ("mla", [0, 7, S - 3, 2], None),
    "ring_window_wraps": ("window", [0, S // 2 - 1, S - 2, S + 3], None),
    "hybrid_stack": ("hybrid", [1, 4, 0, 6], None),
    "verify_advance_zero": ("dense", [0, 4, S - 3, 8], [1, 0, 1, 0]),
    "verify_mla_advance_zero": ("mla", [2, 0, S - 3, 5], [0, 1, 1, 0]),
}


def _run(cfg, params, caches, pos, advance):
    """Three greedy steps (or one three-token verify): tokens and caches."""
    tok = jnp.arange(B, dtype=jnp.int32)[:, None] % cfg.vocab
    pos = jnp.asarray(pos, jnp.int32)
    if advance is not None:
        toks = (tok + jnp.arange(3, dtype=jnp.int32)[None]) % cfg.vocab
        verify = jax.jit(lambda p, t, c: verify_step(p, t, c, pos, cfg, advance=jnp.asarray(advance)))
        logits, caches = verify(params, toks, caches)
        return np.asarray(jnp.argmax(logits, -1)), caches
    step = jax.jit(lambda p, t, c, q: decode_step(p, t, c, q, cfg))
    out = []
    for _ in range(3):
        logits, caches = step(params, tok, caches, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(np.asarray(tok))
        pos = pos + 1
    return np.concatenate(out, 1), caches


@pytest.mark.parametrize("case", list(CASES))
def test_row_write_matches_onehot_select(case, monkeypatch):
    name, pos, advance = CASES[case]
    cfg = _CFGS[name]()
    params = init_params(jax.random.PRNGKey(1), cfg)
    caches = _filled_cache(cfg, jax.random.PRNGKey(2))
    if name == "window":
        assert jax.tree.leaves(caches)[0].shape[2] == S // 2  # a ring of half the positions
    toks, got = _run(cfg, params, caches, pos, advance)
    monkeypatch.setattr(attn_mod, "seq_sharded", lambda axes: True)  # the select
    want_toks, want = _run(cfg, params, caches, pos, advance)
    np.testing.assert_array_equal(toks, want_toks)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))


@pytest.mark.parametrize("layer", [None, 1])
def test_kv_cache_update_writes_only_each_slots_row(layer):
    """Against a NumPy reference: row ``slot[b]`` of sequence ``b`` is
    replaced, every other row is kept, and a slot outside [0, S) (negative
    ones included) writes nothing."""
    rng = np.random.default_rng(0)
    shape = (B, S, 2, 3) if layer is None else (3, B, S, 2, 3)
    cache = rng.standard_normal(shape).astype(np.float32)
    new = rng.standard_normal((B, 1, 2, 3)).astype(np.float32)
    slot = np.array([0, S - 1, S, -1], np.int32)
    got = np.asarray(kv_cache_update(jnp.asarray(cache), jnp.asarray(new), jnp.asarray(slot),
                                     ("batch", "kv_seq", None, None), layer))
    want = cache.copy()
    view = want if layer is None else want[layer]
    for b in range(B):
        if 0 <= slot[b] < S:
            view[b, slot[b]] = new[b, 0]
    np.testing.assert_array_equal(got, want)
