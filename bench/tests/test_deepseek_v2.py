"""The DeepSeek-V2 family (``bench/models/deepseek_v2.py``) and its cell,
``dsv2l.docqa``, on the CPU at the program's reduced DeepSeek-V2-Lite share
(d_model 64, 3 layers, 2 of 8 routed experts held): the reference passes
the sound program and fails its fp8 control, the counts agree with a hand
count and never exceed the work the program does, and whole runs with the
timed path broken read as not correct."""
import json
import time
from types import SimpleNamespace

import jax
import pytest

from bench.models import deepseek_v2 as fam
from bench.tests import tiny

CELL = "dsv2l.docqa"
CONFIG = "bench/configs/deepseek-v2-lite-serve.json"

#: the configuration file cut to the program's reduced share, 3 layers
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 96, "vocab_size": 128, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 32, "n_routed_experts": 2,
    "num_experts_per_tok": 3, "num_hidden_layers": 3,
    "held_experts": {"first": 0, "of": 8, "ranks": 4},
    "rope_scaling": {"original_max_position_embeddings": 64},
    "engine": {"n_slots": 4, "max_seq": 128, "block_size": 16},
    # limit from CPU readings at this size: sound runs 0 to 0.018, the fp8
    # control 0.21 to 0.28 over seeds 0-3
    "checks": {"widest_logit_gap": 0.03, "min_tokens": 20, "sample_tokens": 60, "max_requests": 6},
}
MIX = {"clients": 8, "pool": 256,
       "prompt_len": {"median": 32, "min": 16, "max": 48, "grid": [16, 32, 48]},
       "output_len": {"median": 12, "min": 4, "max": 24}}


def _model(root=tiny.ROOT) -> dict:
    return tiny._merge(json.loads((root / CONFIG).read_text()), MODEL)


@pytest.fixture()
def root(tmp_path, monkeypatch):
    r = tiny.patched_root(tmp_path, monkeypatch)
    (r / CONFIG).write_text(json.dumps(_model()))
    mix = json.loads((tiny.ROOT / "bench" / "traffic" / f"{CELL}.json").read_text())
    (r / "bench" / "traffic" / f"{CELL}.json").write_text(json.dumps(tiny._merge(mix, MIX)))
    return r


def _run(root, seed=2**31 + 5):
    from bench.harness import core

    return core.run_cell(CELL, seed=seed, seconds=2.0, trace=False, t_process=time.perf_counter(),
                         devices=jax.devices(), peaks=tiny.PEAKS, root=root)


def test_sound_run_is_correct(root):
    out = _run(root)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["gen_tokens_per_s"]["value"] > 0 and out["metrics"]["setup_s"]["value"] > 0
    assert out["checks"]["tokens_compared"]["value"] >= MODEL["checks"]["min_tokens"]


def test_decode_that_leaves_its_state_unchanged_is_caught(root, monkeypatch):
    from repro.models import decode_step
    from repro.serving import engine

    real = engine._jitted_steps

    def broken(cfg):
        _, prefill = real(cfg)
        return jax.jit(lambda p, t, c, pos: (decode_step(p, t, c, pos, cfg)[0], c)), prefill

    monkeypatch.setattr(engine, "_jitted_steps", broken)
    assert not _run(root)["correct"]


def test_expert_layer_that_drops_the_held_experts_is_caught(root, monkeypatch):
    """The routed experts' part left out, shared experts kept."""
    from repro.models import moe
    from repro.serving import engine

    real = moe._ragged_moe

    def none_held(p, xt, cfg):
        y, aux = real(p, xt, cfg)
        return 0 * y, aux

    monkeypatch.setattr(moe, "_ragged_moe", none_held)
    monkeypatch.setattr(engine, "_JIT_CACHE", {})  # trace the programs anew
    assert not _run(root)["correct"]


def test_token_altered_where_produced_is_caught(root, monkeypatch):
    from repro.serving import engine

    real = engine.ServeEngine._sample_batch
    monkeypatch.setattr(engine.ServeEngine, "_sample_batch",
                        lambda self, logits: (real(self, logits) + 1) % self.cfg.vocab)
    assert not _run(root)["correct"]


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_fp8_control_fails_where_the_program_passes(root, seed):
    import bench.calibrate as calibrate
    from bench.harness import device as devmod

    line = calibrate.reading(CELL, seed, 2.0, jax.devices(), tiny.PEAKS, devmod.CompileCounter(),
                             root=root)
    lim = MODEL["checks"]["widest_logit_gap"]
    assert line["correct"], line
    assert line["program_widest_logit_gap"] <= lim < line["control_widest_logit_gap"], line
    assert "control_widest_topk_gap" not in line  # greedy traffic: no sampled check


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def test_counts_match_a_hand_count_at_the_tiny_shape():
    m = _model()
    d, h, r, dn, dr, dv, f, fe, v = 64, 4, 16, 16, 8, 16, 96, 32, 128
    attn = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    assert fam.attn_params(m) == attn == 13824
    expert = 3 * d * fe
    # bf16: 1 dense layer, 2 expert layers of 2 shared + 2 held experts, norms;
    # the routers (8 wide) in float32
    weights = (3 * attn + 3 * d * f + 2 * (2 + 2) * expert + 2 * v * d + 3 * (2 * d + r) + d) * 2
    weights += 2 * d * 8 * 4
    assert fam.weight_bytes(m) == weights
    row = 3 * (r + dr) * 2
    assert fam.latent_bytes_per_token(m) == row
    # a token runs every matmul weight, 3 of the 8 experts' share of the 2 held, and the head
    per_tok = 3 * attn + 3 * d * f + 2 * (2 * expert + d * 8 + 3 * 2 / 8 * expert) + d * v
    assert fam.decode_flops(m, [5, 9]) == pytest.approx(2 * 2 * per_tok + 3 * 2 * h * (2 * r + dr) * 14)
    # two tokens leave a held expert unread with probability (5/8)**2
    unread = 2 * 2 * expert * 2 * (5 / 8) ** 2
    want = weights - v * d * 2 - unread + (4 + 8) * row + 2 * row + 2 * d * 2
    assert fam.decode_bytes(m, [5, 9]) == pytest.approx(want)
    t = 10
    causal = 3 * 2 * h * (dn + dr + dv) * (t * (t + 1) // 2)
    assert fam.prefill_flops(m, t) == pytest.approx(2 * t * (per_tok - d * v) + causal + 2 * d * v)
    assert fam.prefill_bytes(m, t) == pytest.approx(
        weights - v * d * 2 - 2 * 2 * expert * 2 * (5 / 8) ** t + t * row + t * d * 2)


def test_counts_never_exceed_what_the_program_computes():
    """The counts, against XLA's own count of the served programs with every
    layer unrolled: a roofline share from the counts can then never pass
    what the program's own work would give, and stays at or under 100%."""
    import jax.numpy as jnp

    from repro.configs import reduced_config
    from repro.models import abstract_cache, abstract_params
    from repro.serving.engine import _jitted_serve_ops, _jitted_steps

    m = dict(_model(), torch_dtype="float32")
    cfg = reduced_config("deepseek-v2-lite-ep8").replace(n_layers=3, dtype="float32",
                                                          scan_layers=False)
    n, S, L = 4, 128, 48
    sds = jax.ShapeDtypeStruct
    decode, _ = _jitted_steps(cfg)
    prefill_prime, _ = _jitted_serve_ops(cfg, S)
    P = abstract_params(cfg)
    dec = decode.lower(P, sds((n, 1), jnp.int32), abstract_cache(cfg, n, S),
                       sds((n,), jnp.int32)).compile().cost_analysis()
    pre = prefill_prime.lower(P, {"tokens": sds((1, L), jnp.int32)}).compile().cost_analysis()
    contexts = [S] * n  # the program attends over every cached position
    assert 0 < fam.decode_flops(m, contexts) <= dec["flops"]
    assert 0 < fam.decode_bytes(m, contexts) <= dec["bytes accessed"]
    assert 0 < fam.prefill_flops(m, L) <= pre["flops"]
    assert 0 < fam.prefill_bytes(m, L) <= pre["bytes accessed"]


def test_expert_tokens_per_step_reads_the_collect_spans(monkeypatch):
    from bench.harness import core
    from repro.core import trace

    buf = trace.SpanBuffer()
    monkeypatch.setattr(trace, "SPANS", buf)
    read = core._reader("expert_tokens_per_step.docqa")
    run = SimpleNamespace(record={"traced_steps": [[1.0, 1.1, 0, [], [], 0], [1.9, 2.0, 0, [], [], 0]]},
                          cell=SimpleNamespace(model={"num_hidden_layers": 27,
                                                      "first_k_dense_replace": 1}))
    assert read(run) is None
    for t0, tokens in ((1.05, 624), (1.5, 208), (2.5, 9999)):  # the last after the stretch
        sp = trace.Span("serve.collect")
        sp.t0, sp.t1 = int(t0 * 1e9), int((t0 + 0.01) * 1e9)
        sp.counts.update(expert_tokens=tokens, expert_tokens_max=40, experts_held=8)
        buf.append(sp)
    buf.append(trace.Span("serve.collect"))  # no counts: a model without them
    assert read(run) == pytest.approx((624 + 208) / (26 * 8) / 2)
