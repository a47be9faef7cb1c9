"""granite-4.0-h-small [hybrid] — Mamba-2 SSD and NoPE attention, every
layer a 72-expert top-10 MoE with a shared expert
(hf:ibm-granite/granite-4.0-h-small, granitemoehybrid, 32B-A9B).
40L d4096: a period of 10 layers, five Mamba-2, one attention, four
Mamba-2 (36 SSD, 4 attention); SSD 128 heads of 64, d_state 128, 1 group,
chunk 256; attention 32H (GQA kv=8) of 128, no positional embedding;
expert d_ff 768, shared expert 1536; tied vocab 100352; granite's
multipliers: embedding 12, attention 1/128, residual 0.22, logits / 16."""
from repro.configs.common import LayerSpec, ModelConfig

_M, _A = LayerSpec("ssd", "moe"), LayerSpec("attn", "moe")
PERIOD = (_M,) * 5 + (_A,) + (_M,) * 4

FULL = ModelConfig(
    name="granite-4.0-h-small", family="hybrid", vocab=100_352,
    d_model=4096, n_layers=40, pattern=PERIOD,
    n_heads=32, n_kv=8, head_dim=128, pos_embed="none", rope_theta=10_000.0,
    d_ff=768, n_experts=72, top_k=10, capacity_factor=1.25,
    moe_group_size=4096, shared_expert=True, shared_d_ff=1536,
    ssm_state=128, ssm_headdim=64, ssm_expand=2, ssm_chunk=256,
    norm_eps=1e-5, embed_mult=12.0, attn_mult=0.0078125, resid_mult=0.22,
    logits_div=16.0,
).validate()

# one period; each layer holds 4 of its router's 8 experts
SMOKE = ModelConfig(
    name="granite4h-smoke", family="hybrid", vocab=128,
    d_model=32, n_layers=10, pattern=PERIOD,
    n_heads=4, n_kv=2, head_dim=8, pos_embed="none",
    d_ff=16, n_experts=4, n_routed_experts=8, expert_offset=2, top_k=3,
    capacity_factor=2.0, moe_group_size=64, shared_expert=True,
    shared_d_ff=24, ssm_state=16, ssm_headdim=8, ssm_expand=2, ssm_chunk=8,
    norm_eps=1e-5, embed_mult=12.0, attn_mult=0.125, resid_mult=0.22,
    logits_div=16.0, vocab_pad_multiple=16,
).validate()
