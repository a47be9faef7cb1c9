"""The chip benchmark's one command.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and its files under ``chipbench/``
(``chipbench.spec``), runs the cell's job on the chip, checks what the
timed path produced against the plain reference, and prints one JSON line
last: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``: each compared
number with its limit.  The same numbers close standard error.

Exits 2, printing no result, where JAX finds no TPU, fewer chips than the
cell asks for, a chip whose peaks are not listed, or no program beside the
benchmark.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def main(argv=None, *, look_for_chip: bool = True, hooks=None, root=None,
         bench_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        return _fail(f"no program under {src}")
    sys.path.insert(0, str(src))
    from chipbench import common, peaks, spec, trace

    bench = spec.load_json(Path(bench_path or CHECKOUT / "BENCHMARK.json"))
    cell = spec.load_cell(bench, args.workload, Path(root or spec.ROOT))

    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if look_for_chip:
        if devices[0].platform != "tpu":
            return _fail(f"no TPU: JAX found {devices[0].platform}")
        if len(devices) < cell.chips:
            return _fail(f"{cell.name} needs {cell.chips} chips, JAX found "
                         f"{len(devices)}")
        try:
            peak = peaks.peaks(kind)
        except ValueError as e:
            return _fail(str(e))
    else:
        peak = next(iter(peaks.PEAKS.values()))

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = spec.job_module(cell).run(cell, args.seed, args.seconds,
                                    bool(args.trace), common.Clock(_T0),
                                    hooks=hooks)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed}
    if args.trace:
        red = trace.reduce_file(trace.find_xplane(out.trace_dir),
                                common.SPANS)
        shutil.rmtree(out.trace_dir, ignore_errors=True)
        ctx = {"counters": out.counters, "trace": red, "peaks": peak,
               "cell": cell}
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(cell.root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        line["metrics"] = metrics
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            line["breakdown"] = {"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]}
    else:
        line["metrics"] = {m["name"]: {"value": out.end_to_end[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
    line["device"] = device
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    for k, (v, lim) in out.checks.items():
        print(f"check {k}: {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
