"""Host time the training loop waited on the data layer (the program's
``Prefetcher.next``), per step: the harness span around each call, summed
over the window and divided by the steps."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps") or "data_wait_s" not in c:
        return None
    return c["data_wait_s"] / c["steps"] * 1e3
