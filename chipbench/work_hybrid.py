"""Work a step of a Mamba-2 / attention hybrid with sparse experts
(granite-4.0-h, ``granitemoehybrid``) requires, counted from the
configuration's shapes.

As ``chipbench.work`` counts, and with its unembedding and branch: what
the mathematics needs, a multiply-add two FLOPs, elementwise work, norms,
convolutions, softmax and the loss left out.  Per token, a Mamba layer's
projections (z, x, B, C, dt in; out) and the SSD recurrence's
``4·H·P·N`` (the state's decay and update, and its read-out); an
attention layer's projections and causal attention (query ``t`` meets
``t + 1`` keys, no position embedding); on every layer the router over all
``router_experts``, the shared expert, and ``num_experts_per_tok ·
held / router_experts`` expert passes, the share of the choices the
experts held here take.  Nothing for dispatch, capacity padding or chunk
padding.  ``chipbench.work`` itself counts every layer as attention.
"""
from __future__ import annotations

from chipbench import work


def _kinds(cfg) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def mamba_flops_per_token(cfg) -> float:
    """One Mamba-2 mixer's projections and recurrence, per token."""
    d = cfg["hidden_size"]
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner, gn = h * p, cfg["mamba_n_groups"] * n
    proj = 2 * d * (2 * inner + 2 * gn + h) + 2 * inner * d
    return float(proj + 4 * h * p * n)


def attention_proj_flops_per_token(cfg) -> float:
    d, hd = cfg["hidden_size"], work._head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return float(2 * d * hd * (2 * h + 2 * kv))


def moe_flops_per_token(cfg) -> float:
    """Router, shared expert and this chip's share of the expert passes."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    routed = cfg["router_experts"]
    share = cfg["num_local_experts"] / routed
    return float(2 * d * routed
                 + 3 * 2 * d * cfg["shared_intermediate_size"]
                 + cfg["num_experts_per_tok"] * share * 3 * 2 * d * f)


def backbone_forward_flops(cfg, batch: int, seq: int) -> float:
    kinds = _kinds(cfg)
    n_mamba = kinds.count("mamba")
    n_attn = kinds.count("attention")
    per_token = (n_mamba * mamba_flops_per_token(cfg)
                 + n_attn * attention_proj_flops_per_token(cfg)
                 + len(kinds) * moe_flops_per_token(cfg))
    hd = work._head_dim(cfg)
    attn = n_attn * work.causal_attention_flops(
        cfg["num_attention_heads"], hd, seq)
    return float(batch * (seq * per_token + attn))


def train_step_flops(cfg, duplex, batch: int, seq: int) -> float:
    """Duplex step: backbone forward, the unembedding over the vocabulary
    slice forward and back to the hidden state, the branch's forward plus
    a backward of twice that."""
    return (backbone_forward_flops(cfg, batch, seq)
            + 2 * work.unembed_flops(cfg, batch * seq)
            + 3 * work.branch_forward_flops(cfg, duplex, batch, seq))
