"""Unified model-configuration schema for every assigned architecture.

A model is a token embedding + a *pattern* of layer specs repeated
``n_rep`` times (scanned, so the HLO stays compact at 80+ layers) + an
optional non-repeating ``remainder`` + final norm + tied unembedding.

Layer kinds: ``attn`` (global self), ``local`` (sliding window),
``cross`` (cross-attention to a frontend/encoder stream), ``ssd``
(mamba2 mixer), ``lru`` (RG-LRU recurrent block).  Each spec also names
its channel mixer: ``dense`` | ``moe`` | ``none``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str                 # attn | local | cross | ssd | lru
    mlp: str = "dense"        # dense | moe | none


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | vlm | audio
    vocab: int
    d_model: int
    n_layers: int
    pattern: Tuple[LayerSpec, ...]
    remainder: Tuple[LayerSpec, ...] = ()

    # attention
    n_heads: int = 0
    n_kv: int = 0
    head_dim: int = 0
    qkv_bias: bool = False
    rope_theta: Optional[float] = 10000.0
    pos_embed: str = "rope"   # rope | sinusoidal | none
    window: Optional[int] = None
    softcap_attn: Optional[float] = None
    softcap_final: Optional[float] = None
    causal: bool = True
    post_norm: bool = False   # gemma2-style post-sublayer norms
    q_chunk: int = 512
    kv_chunk: int = 1024
    blockwise_threshold: int = 1024
    causal_skip: bool = False  # §Perf knob: skip fully-masked kv chunks
    use_flash: bool = False    # fused Pallas flash attention (TPU runtime)

    # mlp
    d_ff: int = 0
    gated_mlp: bool = True
    act: str = "silu"         # silu | gelu

    # moe
    n_experts: int = 0        # experts this layer holds
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_group_size: int = 1024
    shared_expert: bool = False
    shared_d_ff: int = 0      # the shared expert's width; 0: d_ff
    # expert parallelism: the router scores ``n_routed_experts`` (0: the
    # experts held are all of them); this layer holds ``n_experts`` of
    # them from router output ``expert_offset`` on
    n_routed_experts: int = 0
    expert_offset: int = 0

    # ssm (mamba2)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128

    # lru (recurrentgemma)
    lru_width: int = 0
    conv_width: int = 4
    lru_scan_chunk: Optional[int] = None  # §Perf H2: chunked LRU scan

    # frontends / enc-dec
    encoder: Optional["ModelConfig"] = None   # whisper audio encoder
    n_frontend_tokens: int = 0                # stub frame/patch embeddings
    frontend_dim: int = 0

    # norms / vocab
    norm: str = "rmsnorm"     # rmsnorm | layernorm
    norm_eps: float = 1e-6    # rmsnorm's
    vocab_pad_multiple: int = 256
    embed_scale: bool = False  # gemma-style sqrt(d_model) embedding scale

    # granite's multipliers; the defaults apply none
    embed_mult: float = 1.0   # embeddings times this
    attn_mult: Optional[float] = None  # attention scale; None: 1/sqrt(hd)
    resid_mult: float = 1.0   # each sublayer's output times this, then added
    logits_div: float = 1.0   # logits over this

    # attention families that are quadratic cannot serve 500k contexts
    supports_long_context: bool = False

    @property
    def n_rep(self) -> int:
        body = self.n_layers - len(self.remainder)
        if self.pattern and body % len(self.pattern):
            raise ValueError(
                f"{self.name}: {body} layers not divisible by pattern "
                f"{len(self.pattern)}")
        return body // len(self.pattern) if self.pattern else 0

    def validate(self) -> "ModelConfig":
        _ = self.n_rep
        kinds = {s.kind for s in self.pattern + self.remainder}
        if kinds & {"attn", "local", "cross"}:
            assert self.n_heads and self.n_kv and self.head_dim, self.name
            assert self.n_heads % self.n_kv == 0, self.name
        if any(s.mlp == "moe" for s in self.pattern + self.remainder):
            assert self.n_experts and self.top_k, self.name
            routed = self.n_routed_experts or self.n_experts
            assert self.expert_offset + self.n_experts <= routed, self.name
            assert self.top_k <= routed, self.name
        if "ssd" in kinds:
            assert self.ssm_state, self.name
        if "lru" in kinds:
            assert self.lru_width, self.name
        return self


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""
    name: str                 # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    mode: str                 # train | prefill | decode


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}
