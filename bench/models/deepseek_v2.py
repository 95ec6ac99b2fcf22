"""DeepSeek-V2 decoder (DeepSeek-V2-Lite, arXiv:2405.04434): the weights a
run serves, made from the seed, the plain reference it is checked against,
and the operations and bytes the yardstick counts for it.

The reference follows the published ``modeling_deepseek.py``: pre-norm
RMSNorm; multi-head latent attention in its expanded form (queries projected
directly, as ``q_lora_rank`` is null; keys and values expanded per head from
the normalised 512-wide latent; one shared rope key) with YaRN rope and the
softmax scale ``mscale(mscale_all_dim)² / sqrt(qk head dim)``; the leading
``first_k_dense_replace`` layers with a dense SwiGLU MLP; the rest with a
float32 softmax router over every routed expert, greedy top-k, weights not
renormalised (``norm_topk_prob`` false) and scaled by
``routed_scaling_factor``, the routed experts' SwiGLU outputs weighted and
summed, and the shared experts (one SwiGLU MLP of their summed width)
added; a final RMSNorm and an untied head.  It is written in plain
``jax.numpy`` at float32 under ``highest`` matmul precision, one layer per
call so that it fits beside the served weights, and imports nothing of the
program under test.

Departures, each shared with the program:

- The chip holds ``n_routed_experts`` experts (``held_experts.first`` on)
  of the ``held_experts.of`` the router scores, as one rank of expert
  parallelism does.  Each token's held experts are computed densely, from
  the softmax weights; what the experts held elsewhere would add is left
  out, as it is on this chip.
- The source de-interleaves the rope columns of each query head and of the
  rope key before its rotate-half rotation.  With random weights that is a
  fixed permutation of those columns of ``wq`` and ``wkv_a``; both sides
  rotate the columns as stored.
- RMSNorm weights ``w`` are held as offsets ``w - 1`` (the program's
  layout); the reference reads them back as ``1 + offset``.

Weights are drawn on the device in one jitted call, in the program's
parameter layout and in the served dtype (the router in float32, as the
program keeps it).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.llama import EXACT, FP8, _head, _mm, _rmsnorm, seed_key  # noqa: F401

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


class Dims(NamedTuple):
    d: int  # hidden
    h: int  # heads
    r: int  # kv latent rank
    dn: int  # per-head query/key width without rope
    dr: int  # rope width (queries per head, one shared key)
    dv: int  # per-head value width
    f: int  # dense MLP width (leading layers)
    fe: int  # routed expert width
    shared: int  # shared experts (one MLP of fe * shared)
    held: int  # routed experts held here
    first: int  # the first of them
    experts: int  # routed experts the router scores
    top_k: int
    n_layers: int
    lead: int  # leading dense layers
    v: int
    eps: float
    theta: float
    norm_topk: bool
    routed_scale: float


def dims(m: dict) -> Dims:
    share = m["held_experts"]
    return Dims(
        d=m["hidden_size"], h=m["num_attention_heads"], r=m["kv_lora_rank"],
        dn=m["qk_nope_head_dim"], dr=m["qk_rope_head_dim"], dv=m["v_head_dim"],
        f=m["intermediate_size"], fe=m["moe_intermediate_size"], shared=m["n_shared_experts"],
        held=m["n_routed_experts"], first=share["first"], experts=share["of"],
        top_k=m["num_experts_per_tok"], n_layers=m["num_hidden_layers"],
        lead=m["first_k_dense_replace"], v=m["vocab_size"], eps=m["rms_norm_eps"],
        theta=float(m["rope_theta"]), norm_topk=bool(m["norm_topk_prob"]),
        routed_scale=float(m["routed_scaling_factor"]),
    )


def _attn_shapes(g: Dims, n: int) -> dict:
    d, h, r = g.d, g.h, g.r
    return {
        "wq": ((n, d, h, g.dn + g.dr), d**-0.5),
        "wkv_a": ((n, d, r + g.dr), d**-0.5),
        "kv_norm": ((n, r), 0.1),
        "wk_b": ((n, r, h, g.dn), r**-0.5),
        "wv_b": ((n, r, h, g.dv), r**-0.5),
        "wo": ((n, h, g.dv, d), (h * g.dv) ** -0.5),
    }


def _mlp_shapes(d: int, f: int, lead: tuple) -> dict:
    return {"wi_gate": ((*lead, d, f), d**-0.5), "wi_up": ((*lead, d, f), d**-0.5),
            "wo": ((*lead, f, d), f**-0.5)}


def _shapes(m: dict) -> dict:
    """(shape, std[, dtype]) of every leaf, in the program's parameter layout."""
    g = dims(m)
    d, n0, n = g.d, g.lead, g.n_layers - g.lead
    norm = 0.1  # spread of the RMSNorm weights around 1
    return {
        "embedding": ((g.v, d), 1.0),
        "unembed": ((d, g.v), d**-0.5),
        "final_norm": ((d,), norm),
        "lead": {
            "ln1": ((n0, d), norm), "ln2": ((n0, d), norm),
            "attn": _attn_shapes(g, n0),
            "mlp": _mlp_shapes(d, g.f, (n0,)),
        },
        "layers": {
            "ln1": ((n, d), norm), "ln2": ((n, d), norm),
            "attn": _attn_shapes(g, n),
            "moe": {
                "router": ((n, d, g.experts), d**-0.5, "float32"),
                **_mlp_shapes(d, g.fe, (n, g.held)),
                "shared": _mlp_shapes(d, g.fe * g.shared, (n,)),
            },
        },
    }


def make_params(m: dict, seed: int):
    """Every weight of the served model, drawn on the device in one jitted
    call from ``seed``, in ``m``'s dtype (the router in float32)."""
    spec = _shapes(m)
    leaves, tree = jax.tree.flatten(spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    dtype = jnp.dtype(m["torch_dtype"])

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [
            (jax.random.normal(k, leaf[0], jnp.float32) * leaf[1]).astype(
                jnp.dtype(leaf[2]) if len(leaf) > 2 else dtype)
            for k, leaf in zip(keys, leaves)
        ])

    return draw(seed_key(seed))


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    base = theta ** (np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    extra, inter = 1.0 / base, 1.0 / (np.float32(rs["factor"]) * base)
    orig = rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def rope_tables(m: dict, t: int):
    """(cos, sin) of positions 0..t-1, (t, 1, dr/2), and the softmax scale."""
    g = dims(m)
    rs = m["rope_scaling"]
    inv = yarn_inv_freq(g.dr, g.theta, rs)
    gain = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    scale = (g.dn + g.dr) ** -0.5 * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return (jnp.asarray(np.cos(ang) * gain)[:, None, :], jnp.asarray(np.sin(ang) * gain)[:, None, :],
            scale)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(ctrl, h, p, lead: str = ""):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    g = _mm(ctrl, f"td,{lead}df->{lead}tf", h, f32(p["wi_gate"]))
    u = _mm(ctrl, f"td,{lead}df->{lead}tf", h, f32(p["wi_up"]))
    return _mm(ctrl, f"{lead}tf,{lead}fd->{lead}td", jax.nn.silu(g) * u, f32(p["wo"]))


@functools.partial(jax.jit, static_argnames=("ctrl", "g"))
def _layer(x, lp, cos, sin, scale, *, ctrl, g: Dims):
    """One layer; ``lp`` holds ``mlp`` (a leading dense layer) or ``moe``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    a, t = lp["attn"], x.shape[0]
    h = _rmsnorm(x, lp["ln1"], g.eps)
    q = _mm(ctrl, "td,dhk->thk", h, f32(a["wq"]))
    q = jnp.concatenate([q[..., :g.dn], _rope(q[..., g.dn:], cos, sin)], axis=-1)
    kv = _mm(ctrl, "td,dr->tr", h, f32(a["wkv_a"]))
    c = _rmsnorm(kv[:, :g.r], a["kv_norm"], g.eps)
    k_pe = _rope(kv[:, None, g.r:], cos, sin)  # (t, 1, dr): one rope key for every head
    k = jnp.concatenate([_mm(ctrl, "tr,rhk->thk", c, f32(a["wk_b"])),
                         jnp.broadcast_to(k_pe, (t, g.h, g.dr))], axis=-1)
    v = _mm(ctrl, "tr,rhk->thk", c, f32(a["wv_b"]))
    s = _mm(ctrl, "qhk,shk->hqs", q, k) * scale
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm(ctrl, "hqs,shk->qhk", p, v)
    x = x + _mm(ctrl, "thk,hkd->td", o, f32(a["wo"]))
    h = _rmsnorm(x, lp["ln2"], g.eps)
    if "mlp" in lp:
        return x + _swiglu(ctrl, h, lp["mlp"])
    e = lp["moe"]
    probs = jax.nn.softmax(_mm(ctrl, "td,de->te", h, f32(e["router"])), axis=-1)
    w, idx = jax.lax.top_k(probs, g.top_k)
    if g.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    else:
        w = w * g.routed_scale
    held = g.first + jnp.arange(g.held)
    gate = jnp.sum(jnp.where(idx[None] == held[:, None, None], w[None], 0.0), axis=-1)  # (held, t)
    routed = jnp.sum(gate[..., None] * _swiglu(ctrl, h, e, "e"), axis=0)
    return x + routed + _swiglu(ctrl, h, e["shared"])


def reference(m: dict, params, tokens: np.ndarray, served: np.ndarray, *, k: int = 1,
              ctrl: int = EXACT, pad_to: int):
    """Teacher-forced forward over ``tokens`` (padded at the end to
    ``pad_to``, which causal attention makes harmless).  At each position
    ``i`` it returns the ``k``-th best logit, the logit of ``served[i]`` and
    the token ranked ``k``-th (``k`` = 1: the best logit and the argmax),
    each as a host array over the unpadded positions."""
    g = dims(m)
    n = len(tokens)
    toks = np.zeros(pad_to, np.int32)
    toks[:n] = tokens
    srv = np.zeros(pad_to, np.int32)
    srv[:n] = served
    cos, sin, scale = rope_tables(m, pad_to)
    x = params["embedding"][jnp.asarray(toks)].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for stack, count in (("lead", g.lead), ("layers", g.n_layers - g.lead)):
            for i in range(count):
                lp = jax.tree.map(lambda a: a[i], params[stack])
                x = _layer(x, lp, cos, sin, scale, ctrl=ctrl, g=g)
        at_k, at, kth = _head(x, params["final_norm"], params["unembed"], jnp.asarray(srv), k=k,
                              ctrl=ctrl, eps=g.eps)
    return np.asarray(at_k)[:n], np.asarray(at)[:n], np.asarray(kth)[:n]


# ---------------------------------------------------------------------------
# the yardstick's counts: what the algorithm needs, from shapes alone
# ---------------------------------------------------------------------------

def dtype_bytes(m: dict) -> int:
    return BYTES[m["torch_dtype"]]


def attn_params(m: dict) -> int:
    """Matmul weights of one latent-attention block."""
    g = dims(m)
    return (g.d * g.h * (g.dn + g.dr) + g.d * (g.r + g.dr) + g.r * g.h * (g.dn + g.dv)
            + g.h * g.dv * g.d)


def expert_params(m: dict) -> int:
    g = dims(m)
    return 3 * g.d * g.fe


def _per_token_matmul_params(m: dict) -> float:
    """Matmul weights one token runs through, every layer and the head:
    attention, the leading dense MLPs, the shared experts, the router and,
    of the held experts, the expected ``top_k * held / experts``."""
    g = dims(m)
    n_moe = g.n_layers - g.lead
    routed = g.top_k * g.held / g.experts * expert_params(m)
    return (g.n_layers * attn_params(m) + g.lead * 3 * g.d * g.f
            + n_moe * (g.shared * expert_params(m) + g.d * g.experts + routed) + g.d * g.v)


def latent_bytes_per_token(m: dict) -> int:
    """The cached latent and rope key of one position of one sequence, all layers."""
    g = dims(m)
    return g.n_layers * (g.r + g.dr) * dtype_bytes(m)


def weight_bytes(m: dict) -> int:
    """Every weight this chip holds (embedding included, router in float32)."""
    g = dims(m)
    b = dtype_bytes(m)
    n_moe = g.n_layers - g.lead
    norms = g.n_layers * (2 * g.d + g.r) + g.d
    dense = (g.n_layers * attn_params(m) + g.lead * 3 * g.d * g.f
             + n_moe * (g.shared + g.held) * expert_params(m) + 2 * g.v * g.d + norms)
    return dense * b + n_moe * g.d * g.experts * 4


def _weights_read(m: dict, tokens: int) -> float:
    """Weight bytes a pass over ``tokens`` tokens reads: everything but the
    embedding once, except that a held expert is read only if one of the
    tokens chose it, with probability ``1 - (1 - top_k/experts)**tokens``."""
    g = dims(m)
    n_moe = g.n_layers - g.lead
    unread = g.held * expert_params(m) * dtype_bytes(m) * n_moe * (1 - g.top_k / g.experts) ** tokens
    return weight_bytes(m) - g.v * g.d * dtype_bytes(m) - unread


def decode_bytes(m: dict, contexts: Sequence[int]) -> float:
    """HBM bytes one decode step needs for sequences that attend to
    ``contexts`` positions each (the new token's included): the weights
    read (``_weights_read`` over the step's tokens), the embedding rows
    looked up, each sequence's cached latent rows read and one new row per
    sequence written."""
    b = len(contexts)
    per_tok = latent_bytes_per_token(m)
    kv_read = sum(max(c - 1, 0) for c in contexts) * per_tok
    return (_weights_read(m, b) + kv_read + b * per_tok
            + b * dims(m).d * dtype_bytes(m))


def decode_flops(m: dict, contexts: Sequence[int]) -> float:
    """One token per sequence: the matmul weights it runs through, and the
    absorbed latent attention over its context (scores over the latent and
    rope key, 2r + dr multiply-adds per head and position, as the decode
    computes it from the cache), 2 FLOPs per multiply-add."""
    g = dims(m)
    per_pos = g.n_layers * 2 * g.h * (2 * g.r + g.dr)
    return len(contexts) * 2 * _per_token_matmul_params(m) + per_pos * sum(contexts)


def prefill_flops(m: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens: the matmul weights per token (the
    head for the last position only), and causal attention with per-head
    keys and values expanded from the latent (query i attends to i + 1
    keys: dn + dr for the score, dv for the sum)."""
    g = dims(m)
    t = prompt_len
    causal = g.n_layers * 2 * g.h * (g.dn + g.dr + g.dv) * (t * (t + 1) // 2)
    return 2 * t * (_per_token_matmul_params(m) - g.d * g.v) + causal + 2 * g.d * g.v


def prefill_bytes(m: dict, prompt_len: int) -> float:
    """The weights a prompt reads, its embedding rows, and its latent rows written."""
    t = prompt_len
    return (_weights_read(m, t) + t * latent_bytes_per_token(m) + t * dims(m).d * dtype_bytes(m))
