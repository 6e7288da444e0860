"""moonlight-16b-a3b [moe, mla] — DeepSeek-V3 layout [hf:moonshotai/Moonlight-16B-A3B; hf].

27L d_model=2048 16H, multi-head latent attention (kv_lora_rank 512, q
projected directly, qk_nope 128 + qk_rope 64, v 128), vocab 163840, untied
head, rope_theta 50000.  Layer 0 dense (d_ff 11264); layers 1-26 MoE: 64
routed experts, 6 per token, width 1408, 2 shared experts, sigmoid scores
with a selection bias (noaux_tc, one group), top-k normalised, routed
scale 2.446.

Deployment: 8 chips share each MoE layer by expert parallelism; this chip
holds experts 0-7 of every MoE layer and routes over all 64.  Weights are
stored in bf16 as the model is served; the router and its bias in f32.
"""

import dataclasses

from repro.models.config import ArchConfig, MLASpec, MoESpec

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="transformer",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab_size=163840, rope_theta=50000.0, norm_eps=1e-5,
    first_dense=1,
    mla=MLASpec(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128),
    moe=MoESpec(n_experts=64, top_k=6, d_ff_expert=1408, scoring="sigmoid",
                routed_scale=2.446, n_shared=2,
                n_held=8, held_offset=0),
    param_dtype="bfloat16",
)

# one dense layer and two MoE layers, 2 of 8 experts held
SMOKE = dataclasses.replace(
    CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=96, vocab_size=256, q_chunk=32, kv_chunk=32,
    mla=MLASpec(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16),
    moe=dataclasses.replace(CONFIG.moe, n_experts=8, top_k=3, d_ff_expert=32,
                            n_held=2),
    param_dtype="float32",
)
