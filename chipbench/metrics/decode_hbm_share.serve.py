"""Decode's share of the chip's HBM bandwidth: the bytes the window's
decode steps require (``chipbench.work.decode_step_bytes``: each backbone
weight once in bf16, plus the live cache) over the device time of the
decode program (``jit_decode_step`` in the trace) and the peak."""

MODULE = "jit_decode_step"


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not tr["module_s"].get(MODULE) or not c.get("decode_steps"):
        return None
    rate = c["decode_bytes"] / tr["module_s"][MODULE]
    return 100.0 * rate / ctx["peaks"]["hbm_bytes_per_s"]
