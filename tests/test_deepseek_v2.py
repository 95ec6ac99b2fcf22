"""DeepSeek-V2-Lite at a reduced size (``reduced_config``): latent attention
with no query LoRA and YaRN rope, one leading dense layer, 8 routed experts
of which a share holds 2, and shared experts.  The served programs agree
with a plain float32 forward pass, the shares of an expert layer add up to
the whole layer, no token is dropped, and the published formulas hold."""
from __future__ import annotations

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.layers import mlp_apply, yarn_frequencies, yarn_mscale
from repro.models.mla import softmax_scale
from repro.models.moe import _router, moe_apply, moe_defs
from repro.models.param import init_tree
from repro.serving import ServeEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

pytestmark = pytest.mark.timeout(300)

SHARE = "deepseek-v2-lite-ep8"
LAYERS = 3  # the leading dense layer and two expert layers


def _model_file(cfg) -> dict:
    """The benchmark's configuration file cut to ``cfg``'s reduced widths,
    so that the benchmark's plain reference reads the same model."""
    m = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite-serve.json").read_text())
    mla, moe = cfg.mla, cfg.moe
    m.update(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads, intermediate_size=cfg.d_ff,
        vocab_size=cfg.vocab, kv_lora_rank=mla.kv_lora_rank, qk_nope_head_dim=mla.qk_nope_head_dim,
        qk_rope_head_dim=mla.qk_rope_head_dim, v_head_dim=mla.v_head_dim,
        moe_intermediate_size=moe.d_ff_expert, n_routed_experts=moe.n_held,
        num_experts_per_tok=moe.top_k, num_hidden_layers=cfg.n_layers, torch_dtype=cfg.dtype,
        held_experts={"first": moe.held[0], "of": moe.n_experts, "ranks": moe.n_experts // moe.n_held},
        rope_scaling=dict(m["rope_scaling"], original_max_position_embeddings=(
            cfg.rope_scaling.original_max_position_embeddings)),
    )
    return m


def _reference_logits(m, params, tokens) -> np.ndarray:
    """Every position's logits from the benchmark's float32 reference."""
    from bench.models import deepseek_v2 as fam
    from bench.models.llama import _rmsnorm

    g = fam.dims(m)
    cos, sin, scale = fam.rope_tables(m, len(tokens))
    x = params["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for stack, count in (("lead", g.lead), ("layers", g.n_layers - g.lead)):
            for i in range(count):
                lp = jax.tree.map(lambda a: a[i], params[stack])
                x = fam._layer(x, lp, cos, sin, scale, ctrl=fam.EXACT, g=g)
        x = _rmsnorm(x, params["final_norm"], g.eps)
        return np.asarray(x @ params["unembed"].astype(jnp.float32))


@pytest.fixture(scope="module")
def served():
    cfg = reduced_config(SHARE).replace(n_layers=LAYERS, dtype="float32")
    m = _model_file(cfg)
    from bench.models import deepseek_v2 as fam

    return cfg, m, fam.make_params(m, 7)


def test_reduced_share_has_every_mechanism(served):
    cfg = served[0]
    assert cfg.mla.q_lora_rank is None and cfg.rope_scaling is not None
    assert cfg.moe.first_k_dense == 1 and cfg.moe.n_shared_experts == 2
    assert (cfg.moe.n_experts, cfg.moe.held, cfg.moe.norm_topk_prob) == (8, (0, 2), False)


def test_prefill_then_decode_through_the_engine_cache_match_the_reference(served):
    """Two requests at different positions share the engine's slot cache:
    each is prefilled and installed, then decoded teacher-forced; every
    step's logits must equal the plain float32 forward pass's.  Tolerance
    2e-4: float32 rounding only, the absorbed decode summing the latent
    products in another order than the expanded reference over 3 layers."""
    cfg, m, params = served
    rng = np.random.default_rng(0)
    lens, steps = (11, 6), 5
    seqs = [rng.integers(0, cfg.vocab, size=n + steps).astype(np.int32) for n in lens]
    with ServeEngine(cfg, params, n_slots=3, max_seq=32, block_size=4) as eng:
        caches, tok = eng._caches, eng._last_tok
        for slot, (n, seq) in enumerate(zip(lens, seqs)):
            last, primed, routed = eng._prefill_prime(params, {"tokens": jnp.asarray(seq[None, :n])})
            want = _reference_logits(m, params, seq[:n])[-1]
            np.testing.assert_allclose(np.asarray(last[0]), want, rtol=2e-4, atol=2e-4)
            assert routed.shape == (LAYERS - 1, 2)
            caches, tok = eng._install(caches, primed, tok, jnp.int32(slot), jnp.int32(seq[n]))
        pos = np.array([lens[0], lens[1], 0], np.int32)
        for s in range(steps - 1):
            logits, caches, routed = eng._decode(params, tok, caches, jnp.asarray(pos))
            assert routed.shape == (LAYERS - 1, 2)
            for slot, seq in enumerate(seqs):
                want = _reference_logits(m, params, seq[:pos[slot] + 1])[-1]
                np.testing.assert_allclose(np.asarray(logits[slot, 0]), want, rtol=2e-4,
                                           atol=2e-4, err_msg=f"slot {slot} step {s}")
            pos[:2] += 1
            tok = tok.at[0, 0].set(int(seqs[0][pos[0]])).at[1, 0].set(int(seqs[1][pos[1]]))


def _layer_setup(held, **moe):
    cfg = reduced_config(SHARE).replace(dtype="float32")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held=held, **moe))
    return cfg, init_tree(moe_defs(cfg.replace(moe=dataclasses.replace(cfg.moe, held=None))),
                          jax.random.PRNGKey(3), "float32")


def test_shares_of_an_expert_layer_sum_to_the_whole_layer():
    """Every 2-expert share computes its experts' part of the routed output;
    the shares' parts, with the shared experts counted once, equal the layer
    that holds all 8 (the uncut layer)."""
    cfg_all, p = _layer_setup(None)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg_all.d_model), jnp.float32)
    whole, aux = moe_apply(p, x, cfg_all)
    shared = mlp_apply(p["shared"], x, cfg_all.act)
    parts = []
    for first in range(0, 8, 2):
        cfg, _ = _layer_setup((first, 2))
        held = {k: (v[first:first + 2] if k in ("wi_gate", "wi_up", "wo") else v) for k, v in p.items()}
        y, a = moe_apply(held, x, cfg)
        parts.append(y - shared)
        np.testing.assert_array_equal(np.asarray(a["expert_tokens"]),
                                      np.asarray(aux["expert_tokens"][first:first + 2]))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert int(aux["expert_tokens"].sum()) == 2 * 24 * cfg_all.moe.top_k


def test_no_token_is_dropped_when_every_token_routes_to_one_expert():
    """The router sends every token to expert 0 first: all of them are
    computed there, with the weights the softmax gives (no capacity)."""
    cfg, p = _layer_setup(None)
    T, D = 64, cfg.d_model
    p = dict(p, router=jnp.zeros_like(p["router"]).at[:, 0].set(1.0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (1, T, D), jnp.float32)) + 0.5
    y, aux = moe_apply(p, x, cfg)
    assert int(aux["expert_tokens"][0]) == T  # a capacity of 1.25 x T x k / E would hold 30
    xt = x[0]
    top_p, top_i, _ = _router(p, xt, cfg)
    want = mlp_apply(p["shared"], xt, cfg.act)
    for k in range(cfg.moe.top_k):
        for e in range(cfg.moe.n_experts):
            pe = {n: p[n][e] for n in ("wi_gate", "wi_up", "wo")}
            sel = (top_i[:, k] == e)[:, None]
            want = want + jnp.where(sel, top_p[:, k:k + 1] * mlp_apply(pe, xt, cfg.act), 0.0)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_yarn_frequencies_and_mscale_follow_the_published_formula():
    cfg = get_config(SHARE)
    y, dim, base = cfg.rope_scaling, cfg.mla.qk_rope_head_dim, cfg.rope_theta
    # DeepseekV2YarnRotaryEmbedding, in float64
    corr = lambda rot: dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))  # noqa: E731
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    assert (low, high) == (10, 23)
    i = np.arange(dim // 2)
    extra = 1.0 / base ** (2 * i / dim)
    mask = 1.0 - np.clip((i - low) / (high - low), 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(yarn_frequencies(dim, base, y), want, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(y.factor, y.mscale_all_dim) == pytest.approx(mscale, rel=1e-12)
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert softmax_scale(cfg) == pytest.approx(mscale**2 / math.sqrt(192), rel=1e-12)


def test_norm_topk_prob_false_leaves_the_weights_as_the_softmax_gives():
    cfg, p = _layer_setup(None)
    x = jax.random.normal(jax.random.PRNGKey(6), (16, cfg.d_model), jnp.float32)
    top_p, top_i, _ = _router(p, x, cfg)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    np.testing.assert_allclose(np.asarray(top_p),
                               np.asarray(jnp.take_along_axis(probs, top_i, axis=-1)), rtol=1e-6)
    assert float(jnp.max(jnp.sum(top_p, axis=-1))) < 0.99
    scaled, _, _ = _router(p, x, cfg.replace(moe=dataclasses.replace(cfg.moe, routed_scaling_factor=2.5)))
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(top_p), rtol=1e-6)


def test_qwen3_moe_keeps_its_renormalised_top_k():
    """qwen3-moe keeps the default: its top-k weights sum to one, and its
    layer computes exactly the renormalised mixture it always did."""
    cfg = reduced_config("qwen3-moe-235b-a22b").replace(dtype="float32")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    assert cfg.moe.norm_topk_prob and cfg.moe.dispatch == "einsum"
    p = init_tree(moe_defs(cfg), jax.random.PRNGKey(0), "float32")
    xt = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model), jnp.float32)
    top_p, top_i, _ = _router(p, xt, cfg)
    probs = jax.nn.softmax(xt @ p["router"], axis=-1)
    raw = jnp.take_along_axis(probs, top_i, axis=-1)
    np.testing.assert_allclose(np.asarray(top_p), np.asarray(raw / raw.sum(-1, keepdims=True)),
                               rtol=1e-6)
    y, _ = moe_apply(p, xt[None], cfg)
    want = sum(jnp.where((top_i[:, k] == e)[:, None], top_p[:, k:k + 1]
                         * mlp_apply({n: p[n][e] for n in ("wi_gate", "wi_up", "wo")}, xt, cfg.act), 0.0)
               for k in range(cfg.moe.top_k) for e in range(cfg.moe.n_experts))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_param_count_counts_the_dense_layer_and_the_held_share():
    whole, share = get_config("deepseek-v2-lite"), get_config(SHARE)
    assert whole.param_count() == 15_706_470_400  # published: 15.7B
    d, f, fe, mla = 2048, 10944, 1408, 13_762_560
    lead = 2 * d + mla + 3 * d * f  # two norms, latent attention, the dense MLP
    moe = 2 * d + mla + 3 * d * fe * (2 + 8) + d * 64  # 2 shared + 8 held, the router
    assert share.param_count() == 2 * 102400 * d + lead + 26 * moe + d == 3_110_975_488
    # a token runs 6 of 64 experts, so 6 x 8/64 of the share's
    assert share.active_param_count() == whole.active_param_count() - round(26 * 3 * d * fe * 6 * 7 / 8)


def test_served_share_program_matches_the_benchmark_file():
    """Every published width, the 64-wide router, top-6, 2 shared experts,
    27 layers and 8 of 64 experts held, as the benchmark's file states."""
    m = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite-serve.json").read_text())
    cfg = get_config(m["program_arch"])
    mla, moe, y = cfg.mla, cfg.moe, cfg.rope_scaling
    got = dict(hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads, vocab_size=cfg.vocab,
               intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers,
               kv_lora_rank=mla.kv_lora_rank, q_lora_rank=mla.q_lora_rank,
               qk_nope_head_dim=mla.qk_nope_head_dim, qk_rope_head_dim=mla.qk_rope_head_dim,
               v_head_dim=mla.v_head_dim, moe_intermediate_size=moe.d_ff_expert,
               n_routed_experts=moe.n_held, num_experts_per_tok=moe.top_k,
               n_shared_experts=moe.n_shared_experts, first_k_dense_replace=moe.first_k_dense,
               norm_topk_prob=moe.norm_topk_prob, routed_scaling_factor=moe.routed_scaling_factor,
               rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps)
    assert got == {k: m[k] for k in got}
    assert (moe.n_experts, moe.held) == (m["held_experts"]["of"], (m["held_experts"]["first"], 8))
    rs = m["rope_scaling"]
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast, y.beta_slow, y.mscale,
            y.mscale_all_dim) == (rs["factor"], rs["original_max_position_embeddings"],
                                  rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])
    assert m["reduced"] == {"n_routed_experts": m["reduced"]["n_routed_experts"]}
    assert m["reduced"]["n_routed_experts"]["published"] == moe.n_experts == 64
