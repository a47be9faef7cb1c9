"""The whole train step's share of the chip's bf16 peak: the FLOPs the
step requires (``chipbench.work.train_step_flops``) times the steps of the
window, over the window's time and the peak."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps") or not c.get("window_s"):
        return None
    rate = c["steps"] * c["step_flops"] / c["window_s"]
    return 100.0 * rate / ctx["peaks"]["bf16_flops"]
