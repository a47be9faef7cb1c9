"""2D BFP (§III-E) numeric fidelity: quantization error of the paper
format, transpose invariance, and BFP-vs-fp32 training parity.

The Pallas kernels are checked against their oracles by the tests (in
interpret mode) and on the chip by ``chip_smoke.py``; nothing here times
them."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core import bfp


def run() -> list[str]:
    rows = []
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 256))

    for group, mbits in (((3, 3), 5), ((32, 32), 5), ((3, 3), 7)):
        rmse = float(bfp.quantization_rmse(x, group=group, mbits=mbits))
        t = bfp.bfp_quantize(x, group=group, mbits=mbits)
        rows.append(f"bfp/rmse_g{group[0]}m{mbits},0,"
                    f"rmse={rmse:.5f};bits={t.bits_per_value:.2f}")

    # transpose invariance (the §III-E property)
    q1 = bfp.bfp_dequantize(bfp.bfp_quantize(x.T))
    q2 = bfp.bfp_dequantize(bfp.bfp_quantize(x)).T
    rows.append(f"bfp/transpose_invariance,0,"
                f"max_diff={float(jnp.max(jnp.abs(q1-q2))):.2e}")

    # end-to-end: duplex training with paper-format BFP vs fp32 branch
    backbone, _ = common.pretrain_backbone(steps=120)
    l_fp, a_fp, _ = common.train_arm("duplex", backbone, steps=150,
                                     dcfg=common.duplex_cfg(bfp=False))
    l_q, a_q, _ = common.train_arm("duplex", backbone, steps=150,
                                   dcfg=common.duplex_cfg(bfp=True))
    rows.append(f"bfp/training_parity,0,"
                f"fp32_loss={l_fp:.4f};bfp_loss={l_q:.4f};"
                f"gap={(l_q-l_fp):.4f}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
