"""What the job drivers share: the program's configuration, the device's
memory reading, harness spans, and the comparison by the worst leaf."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import statistics
import time

import jax
import numpy as np

# harness spans that may name an idle gap of the device
SPANS = ("data", "dispatch", "sync", "prefill", "decode", "token_fetch")


@dataclasses.dataclass
class Outcome:
    """What a job's run hands back to the harness."""
    end_to_end: dict            # metric name -> value
    counters: dict              # what per-layer readers read
    checks: dict                # compared number -> (value, limit)
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace_dir: str | None = None

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            math.isfinite(v) and v <= lim for v, lim in self.checks.values())


@contextlib.contextmanager
def span(name: str):
    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def no_gc():
    """No garbage collection inside the timed window, as ``timeit`` does.

    A process that holds compiled programs has a large heap, and a full
    collection of it stalls the host loop between two decode steps for
    tens of milliseconds.  What set-up left is frozen out of later
    collections, and collection resumes when the window closes."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


def program_config(cfg: dict):
    """(registry entry, ModelConfig) as the configuration file states it,
    checked against the file's own numbers."""
    from repro.models import registry
    prog = cfg["program"]
    entry = registry.get(prog["arch"])
    mc = dataclasses.replace(entry.config(prog.get("preset", "full")),
                             **prog.get("overrides", {}))
    expect = {"d_model": cfg["hidden_size"],
              "n_layers": cfg["num_hidden_layers"],
              "n_heads": cfg["num_attention_heads"],
              "n_kv": cfg["num_key_value_heads"],
              "head_dim": cfg.get("head_dim") or
              cfg["hidden_size"] // cfg["num_attention_heads"],
              "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
              "rope_theta": cfg["rope_theta"],
              "vocab_pad_multiple": cfg["vocab_pad_multiple"],
              "n_experts": cfg.get("num_local_experts", 0),
              "top_k": cfg.get("num_experts_per_tok", 0)}
    if expect["n_experts"]:
        expect.update(capacity_factor=cfg["capacity_factor"],
                      moe_group_size=cfg["moe_group_size"])
    got = {k: getattr(mc, k) for k in expect}
    if got != expect:
        raise ValueError(f"program config differs from the file: "
                         f"{ {k: (got[k], expect[k]) for k in expect if got[k] != expect[k]} }")
    return entry, mc.validate()


def flat(tree) -> dict:
    from chipbench.weights import path_of
    return {path_of(kp): x for kp, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def check_shapes(tree, want: dict, what: str) -> None:
    got = {p: tuple(x.shape) for p, x in flat(tree).items()}
    want = {p: tuple(s) for p, s in want.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"{what}: program and reference layouts differ: "
                         f"{diff[:6]}")


def memory_peak_bytes(devices) -> int:
    """Peak of the fullest device: buffers plus program scratch."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0))
                     + int(st.get("peak_bytes_reserved", 0)))
    return max(peaks)


def compiled_memory(jitted, *args) -> dict:
    """The compiler's own account of a program's device memory, in bytes."""
    m = jitted.lower(*args).compile().memory_analysis()
    return {k: int(getattr(m, k + "_size_in_bytes"))
            for k in ("argument", "output", "temp", "alias")}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Largest |prog - ref| / max(ref, median ref) over leaves of norms."""
    names = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in names)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in names)


def leaf_norms(tree) -> dict:
    return {p: float(np.linalg.norm(np.asarray(x, np.float64)))
            for p, x in flat(tree).items()}


class Clock:
    """Seconds since the process started its harness."""

    def __init__(self, t0: float):
        self.t0 = t0

    def __call__(self) -> float:
        return time.perf_counter() - self.t0
