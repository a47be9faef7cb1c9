"""Prefill's share of the chip's bf16 peak: the FLOPs the window's
prefills require (``chipbench.work.prefill_flops``) over the device time of
the prefill program (``jit_prefill_step`` in the trace) and the peak."""

MODULE = "jit_prefill_step"


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not tr["module_s"].get(MODULE) or not c.get("rounds"):
        return None
    rate = c["prefill_flops"] / tr["module_s"][MODULE]
    return 100.0 * rate / ctx["peaks"]["bf16_flops"]
