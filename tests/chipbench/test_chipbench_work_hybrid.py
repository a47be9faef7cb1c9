"""Required work of the Mamba-2 / attention hybrid
(``chipbench.work_hybrid``) against counts made by hand."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench import work, work_hybrid

CFG = json.loads((Path(__file__).resolve().parents[2] / "chipbench" /
                  "configs" / "granite-4.0-h-small-ep8.json").read_text())

# granite-4.0-h stage, per token.  A Mamba-2 mixer: z and x 2*4096*8192
# each, B and C 2*4096*128 each, dt 2*4096*128, out 2*8192*4096; the SSD
# recurrence 4*128*64*128.
MAMBA = 2 * 4096 * (2 * 8192 + 2 * 128 + 128) + 2 * 8192 * 4096 \
    + 4 * 128 * 64 * 128
# every layer's MoE: router 2*4096*72, shared expert 3*2*4096*1536, and
# 10 choices a token of which 9/72 fall on the experts held, 3*2*4096*768
MOE = 2 * 4096 * 72 + 6 * 4096 * 1536 + 10 * 9 * 6 * 4096 * 768 // 72
# the attention layer's q, k, v, o; its causal attention over one
# 4096-token sequence, 32 heads of 128, no position embedding
ATTN = 2 * 4096 * 128 * (2 * 32 + 2 * 8)
ATTN_SEQ = 4 * 32 * 128 * 4096 * 4097 // 2
# 9 Mamba layers, 1 attention layer, 10 MoE, over 8 sequences
BACKBONE = 8 * (4096 * (9 * MAMBA + ATTN + 10 * MOE) + ATTN_SEQ)
# the unembedding over the 12,544-id slice, forward and back
UNEMBED = 2 * 8 * 4096 * 4096 * 12544
# the branch: d 4096, d_branch 512, 8 blocks of 4 heads, ff 2048, 256
# pooled rows a sequence (as chipbench.work counts it), three times
BRANCH_ROW = (2 * 2 * 4096 * 512 + 8 * 2 * 4096 * 512
              + 8 * (4 * 2 * 512 * 512 + 3 * 2 * 512 * 2048)
              + 2 * 2 * 512 * 4096)
BRANCH = 8 * (256 * BRANCH_ROW + 8 * 4 * 4 * 128 * 256 * 257 // 2)


def test_parts_by_hand():
    assert MAMBA == 208_666_624 and MOE == 61_931_520
    assert work_hybrid.mamba_flops_per_token(CFG) == MAMBA
    assert work_hybrid.moe_flops_per_token(CFG) == MOE
    assert work_hybrid.attention_proj_flops_per_token(CFG) == ATTN
    assert work_hybrid.backbone_forward_flops(CFG, 8, 4096) == BACKBONE
    assert work.unembed_flops(CFG, 8 * 4096) == UNEMBED
    assert work.branch_forward_flops(CFG, CFG["duplex"], 8, 4096) == BRANCH


def test_train_step_by_hand():
    want = BACKBONE + 2 * UNEMBED + 3 * BRANCH
    assert want == 93_149_569_482_752
    assert work_hybrid.train_step_flops(CFG, CFG["duplex"], 8, 4096) == want
    # the nine mixers are two thirds of the step
    assert 0.65 < 8 * 4096 * 9 * MAMBA / want < 0.67
