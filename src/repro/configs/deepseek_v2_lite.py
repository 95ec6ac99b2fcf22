"""DeepSeek-V2-Lite [moe, MLA] — latent attention with no query LoRA, YaRN
rope, one leading dense layer, then 26 expert layers of 64 routed experts
(top-6, softmax, weights not renormalised) and 2 shared experts
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434].
27L d_model=2048 16H, qk 128+64, v 128, kv_lora=512, dense d_ff=10944,
expert d_ff=1408, vocab=102400.

``SHARE`` is what one chip holds when eight chips split every expert layer
8 experts each (expert parallelism; attention, the dense layer, the shared
experts, the embedding and the head whole on every chip): experts 0-7.  Its
router still scores all 64."""
import dataclasses

from repro.models.config import ArchConfig, MLAConfig, MoEConfig, YarnConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    vocab=102400,
    act="swiglu",
    rope_theta=10000.0,
    rope_scaling=YarnConfig(
        factor=40.0,
        original_max_position_embeddings=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    logits_chunk=1024,
    # served prompts of any length up to a few thousand use the
    # materialising attention (the blockwise one needs multiples of its block)
    attn_blockwise_min_seq=4096,
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        d_ff_expert=1408,
        n_shared_experts=2,
        dispatch="ragged",
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        first_k_dense=1,
    ),
)

SHARE = CONFIG.replace(
    name="deepseek-v2-lite-ep8",
    moe=dataclasses.replace(CONFIG.moe, held=(0, 8)),
)
