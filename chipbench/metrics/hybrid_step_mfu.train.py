"""The whole train step's share of the chip's bf16 peak on a Mamba-2 /
attention hybrid: the FLOPs its step requires
(``chipbench.work_hybrid.train_step_flops``, from the cell's configuration
and traffic) times the steps of the window, over the window's time and
the peak.  None where the run counted no steps or the configuration is not
such a hybrid."""


def read(ctx):
    from chipbench import work_hybrid
    c, cell = ctx["counters"], ctx["cell"]
    if not c.get("steps") or not c.get("window_s") \
            or "mamba_n_heads" not in cell.config:
        return None
    tr = cell.traffic
    flops = work_hybrid.train_step_flops(cell.config, cell.config["duplex"],
                                         tr["batch"], tr["seq"])
    return 100.0 * c["steps"] * flops / c["window_s"] \
        / ctx["peaks"]["bf16_flops"]
