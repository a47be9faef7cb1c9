"""Required work (``chipbench.work``) against counts made by hand, and the
peaks table (``chipbench.peaks``)."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import peaks, work

CONFIGS = Path(__file__).resolve().parents[2] / "chipbench" / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# granite-moe-1b-a400m, one layer per token: q, k, v, o
# 2*1024*64*(2*16 + 2*8) = 6,291,456; router 2*1024*32 = 65,536; 8 experts
# of 3 matmuls 2*1024*512 = 25,165,824; together 31,522,816.  Causal
# attention of one 4096-token sequence: 4*16*64*4096*4097/2 =
# 34,368,126,976.  Backbone, 8 sequences x 24 layers:
# 8*24*(4096*31,522,816 + 34,368,126,976) = 31,389,231,611,904.  Unembed:
# 2*32768*1024*49155 = 3,298,736,209,920, forward and backward.  Branch
# (d 256, 8 blocks, 4 heads, pooled 256 rows): 49,400,512,512, and three
# times that with its backward.
MOE_TRAIN = 31_389_231_611_904 + 2 * 3_298_736_209_920 + 3 * 49_400_512_512
# granite-3-8b-stage10, one layer per token: 2*4096*128*(64 + 16) +
# 6*4096*12800 = 398,458,880.
G8B_TRAIN_8x4096 = 168_689_185_849_344
G8B_TRAIN_2x16384 = 201_713_189_388_288


@pytest.mark.parametrize("name,batch,seq,want", [
    ("granite-moe-1b-a400m", 8, 4096, MOE_TRAIN),
    ("granite-3-8b-stage10", 8, 4096, G8B_TRAIN_8x4096),
    ("granite-3-8b-stage10", 2, 16384, G8B_TRAIN_2x16384),
])
def test_train_step_flops_by_hand(name, batch, seq, want):
    cfg = _cfg(name)
    assert work.train_step_flops(cfg, cfg["duplex"], batch, seq) == want


def test_moe_parts_by_hand():
    cfg = _cfg("granite-moe-1b-a400m")
    assert work.layer_matmul_flops_per_token(cfg) == 31_522_816
    assert work.causal_attention_flops(16, 64, 4096) == 34_368_126_976
    assert work.backbone_forward_flops(cfg, 8, 4096) == 31_389_231_611_904
    assert work.unembed_flops(cfg, 8 * 4096) == 3_298_736_209_920
    assert work.branch_forward_flops(cfg, cfg["duplex"], 8, 4096) == \
        49_400_512_512


def test_serve_work_by_hand():
    cfg = _cfg("granite-moe-1b-a400m")
    # 16*24*(2048*31,522,816 + 4*16*64*2048*2049/2) + 2*16*1024*49155
    assert work.prefill_flops(cfg, 16, 2048) == 28_092_307_439_616
    # weights: 24*(6,291,456/2 ... per layer 3,145,728 attention + 32 experts
    # x (3*1024*512 + 1024) + 2*1024 norms) + 49155*1024 + 1024
    assert work.backbone_params(cfg) == 1_334_628_352
    # 2 bytes a weight, plus k and v of 2049 positions of 16 sequences
    assert work.decode_step_bytes(cfg, 16, 2049) == 4_280_655_872
    assert work.backbone_params(_cfg("granite-3-8b-stage10")) == \
        2_193_719_296


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
