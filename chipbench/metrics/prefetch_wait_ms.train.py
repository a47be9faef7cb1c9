"""Mean time a ``Prefetcher.next`` call of the window blocked on the data
queue, in ms: the program's own data-layer counter
(``repro.obs.runtime.data``, ``prefetch_wait_s``), timed inside the call,
over its last ``steps`` samples, which are the window's.  It leaves out
the harness span's own cost, which ``data_wait_ms.train`` holds.  None
where the program has no such counter or holds fewer samples."""


def read(ctx):
    steps = ctx["counters"].get("steps")
    try:
        from repro.obs import runtime
    except ImportError:
        return None
    waits = [c.value for c in runtime.data.counter_samples("prefetch_wait_s")]
    if not steps or len(waits) < steps:
        return None
    return 1e3 * sum(waits[-steps:]) / steps
