"""Compiles of the cell's step programs beyond the first of each, in this
process: ``train_step`` in a train cell, ``prefill_step`` and
``decode_step`` in a serve cell.  Read from the program's compile recorder
(``repro.obs.runtime``), which counts each backend compile, a load from
the persistent compilation cache included.  None where the program has no
such recorder or it saw one of the programs never compile."""

PROGRAMS = {"train": ("train_step",), "serve": ("prefill_step", "decode_step")}


def read(ctx):
    try:
        from repro.obs import runtime
    except ImportError:
        return None
    counts = [runtime.backend_compiles(name)
              for name in PROGRAMS.get(ctx["cell"].traffic["job"], ())]
    if not counts or 0 in counts:
        return None
    return sum(n - 1 for n in counts)
