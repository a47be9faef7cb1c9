"""Work a step requires, counted from the configuration's shapes.

Counts are of what the mathematics needs, not of what a program executes:
attention is causal (query ``t`` meets ``t + 1`` keys), each token passes
through exactly ``num_experts_per_tok`` experts, and nothing is counted for
dispatch or combine one-hots, capacity padding, padded vocabulary rows or
recomputation.  A multiply-add is two FLOPs.  Elementwise work, norms,
softmax and the loss are left out: they are small next to the matmuls.

``cfg`` is a configuration file's dict (Hugging Face key names) and
``duplex`` its ``duplex`` group.
"""
from __future__ import annotations


def _head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_flops_per_token(cfg) -> float:
    """One backbone layer's projections and MLP or experts, per token."""
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 2 * d * hd * (2 * h + 2 * kv)            # q, k, v, o
    f = cfg["intermediate_size"]
    experts = cfg.get("num_local_experts", 0)
    if experts:
        mlp = (2 * d * experts                      # router
               + cfg["num_experts_per_tok"] * 3 * 2 * d * f)
    else:
        mlp = 3 * 2 * d * f                         # gated: wi, wg, wo
    return float(attn + mlp)


def causal_attention_flops(heads: int, head_dim: int, seq: int) -> float:
    """Scores and values of one causal sequence: sum over t of 4·H·hd·(t+1)."""
    return 4.0 * heads * head_dim * seq * (seq + 1) / 2


def backbone_forward_flops(cfg, batch: int, seq: int) -> float:
    """The frozen backbone's forward over ``batch`` causal sequences."""
    layers = cfg["num_hidden_layers"]
    per_seq = (seq * layer_matmul_flops_per_token(cfg)
               + causal_attention_flops(cfg["num_attention_heads"],
                                        _head_dim(cfg), seq))
    return float(batch * layers * per_seq)


def unembed_flops(cfg, tokens: int) -> float:
    """Tied unembedding of ``tokens`` positions, real vocabulary only."""
    return 2.0 * tokens * cfg["hidden_size"] * cfg["vocab_size"]


def branch_forward_flops(cfg, duplex, batch: int, seq: int) -> float:
    """The duplex branch over pooled streams (``seq / pool_factor`` rows)."""
    d, db = cfg["hidden_size"], duplex["d_branch"]
    sp = -(-seq // duplex["pool_factor"])
    blocks, heads = duplex["n_blocks"], duplex["branch_heads"]
    ff = db * duplex["branch_ff_mult"]
    per_row = (2 * 2 * d * db                       # in_proj1, in_proj2
               + blocks * 2 * d * db                # one tap projection each
               + blocks * (4 * 2 * db * db          # q, k, v, o
                           + 3 * 2 * db * ff)       # gated MLP
               + 2 * 2 * db * d)                    # out_proj
    attn = blocks * causal_attention_flops(heads, db // heads, sp)
    return float(batch * (sp * per_row + attn))


def train_step_flops(cfg, duplex, batch: int, seq: int) -> float:
    """Duplex step: backbone forward, unembed forward and its backward to
    the hidden state, branch forward plus a backward of twice that."""
    tokens = batch * seq
    return (backbone_forward_flops(cfg, batch, seq)
            + 2 * unembed_flops(cfg, tokens)
            + 3 * branch_forward_flops(cfg, duplex, batch, seq))


def prefill_flops(cfg, batch: int, seq: int) -> float:
    """Prompt forward plus the next-token logits of the last position."""
    return backbone_forward_flops(cfg, batch, seq) + unembed_flops(cfg, batch)


def backbone_params(cfg) -> int:
    """Backbone weights, the tied embedding counted once at the real vocab."""
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    experts = cfg.get("num_local_experts", 0)
    attn = d * hd * (2 * h + 2 * kv)
    mlp = experts * (3 * d * f + d) if experts else 3 * d * f
    norms = 2 * d
    return (cfg["num_hidden_layers"] * (attn + mlp + norms)
            + cfg["vocab_size"] * d + d)


def decode_step_bytes(cfg, batch: int, live_len: int,
                      weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """One decode step: every backbone weight once at the compute precision,
    plus the keys and values of ``live_len`` positions of each sequence."""
    cache = (cfg["num_hidden_layers"] * 2 * batch * live_len
             * cfg["num_key_value_heads"] * _head_dim(cfg) * cache_bytes)
    return float(backbone_params(cfg) * weight_bytes + cache)
