"""Readings that the limits of ``correct`` are set from.

    python -m chipbench.calibrate --workload <cell> --seeds 1,2,3 \
        [--seconds 0] [--control] [--fault half_batch] --out <file.json>

In one process, for each seed: the cell's set-up, a window of
``--seconds``, and the comparison with the reference, as a benchmark run
makes them.  ``--control`` adds, on the same seeds, the reference computed
in the lower precision in the program's place.  ``--fault`` plants one of
``chipbench.faults`` under the timed path.  The benchmark's runs never run
this.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax
    from chipbench import common, faults, spec
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cell = spec.load_cell(spec.load_json(CHECKOUT / "BENCHMARK.json"),
                          args.workload)
    hooks = faults.hooks(args.fault, cell) if args.fault else None
    job = spec.job_module(cell)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = job.run(cell, seed, args.seconds, False,
                      common.Clock(time.perf_counter()), hooks=hooks,
                      control=args.control)
        row = {"seed": seed, "checks": {k: v for k, (v, _) in
                                        out.checks.items()},
               "control": out.counters.get("control"),
               "end_to_end": out.end_to_end,
               "counters": {k: out.counters.get(k) for k in
                            ("check_s", "compiled_memory", "gap_stats")},
               "memory_peak_bytes": out.memory_peak_bytes,
               "run_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"workload": args.workload, "fault": args.fault,
         "device": jax.devices()[0].device_kind, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
