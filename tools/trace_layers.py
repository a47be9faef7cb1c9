"""Device time per model layer, and what the host did in each long idle
gap of the device, from one traced run of a benchmark cell.

    python tools/trace_layers.py --workload <cell> --seed <n> \
        [--seconds 30] [--out chiprun_out/layers.json]

Runs the cell in this process as ``python -m chipbench.run ... --trace 1``
does, with JAX's persistent compilation cache off (so the step programs
compile from this source, scope names included), prints its result line,
and keeps the profiler trace.  Then:

- each device operation's own time in the window (its interval less the
  operations nested in it, as ``chipbench.trace`` counts it) is joined to
  its layer and pass by ``repro.obs.scopes.op_layers`` over the compiled
  text of the step program it ran in (the ``XLA Modules`` event around it
  names the program);
- each idle gap of the device longer than 10 ms is listed with the
  host-plane events that cover it, on every host thread and not only the
  harness's spans, the compile spans of ``repro.obs.runtime`` that overlap
  it, and the data-layer waits recorded in it;
- the backend compiles of each step program are counted.

Run it on the chip: the trace of a CPU run has no device plane.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import io
import json
import sys
import types
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
PROGRAMS = ("train_step", "prefill_step", "decode_step")
GAP_MS = 10.0


class _Spy:
    """A jitted step that lowers itself on its first call's own arguments,
    before they are donated, so that its compiled text can be had after
    the run from the executable that ran.  (Shapes and shardings alone
    lower to a program that compiles anew, with other instruction
    names.)"""

    def __init__(self, jitted):
        self._jitted = jitted
        self.lowered = None

    def __call__(self, *args):
        if self.lowered is None:
            self.lowered = self._jitted.lower(*args)
        return self._jitted(*args)

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def hlo_text(self) -> str:
        return self.lowered.compile().as_text()


def run_kept(argv: list[str]) -> tuple[dict, str, dict, dict]:
    """Run the cell traced; (result line, trace directory, {module name:
    compiled text} of the step programs it ran, {step program: backend
    compiles})."""
    import jax
    from chipbench import run
    from repro.obs import runtime

    spies: list[_Spy] = []
    jit = jax.jit

    def spy_jit(fun, *a, **kw):
        jitted = jit(fun, *a, **kw)
        if getattr(fun, "__name__", "") in PROGRAMS:
            spies.append(_Spy(jitted))
            return spies[-1]
        return jitted

    # compile from this source: the persistent cache's key leaves out
    # source locations, so an entry written by another version of the
    # program would hand back that version's scope names
    jax.config.update("jax_enable_compilation_cache", False)
    kept: list[str] = []
    out = io.StringIO()
    shutil = run.shutil
    jax.jit = spy_jit
    run.shutil = types.SimpleNamespace(
        rmtree=lambda path, **_: kept.append(path))
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(argv + ["--trace", "1"])
    finally:
        jax.jit, run.shutil = jit, shutil
    if rc != 0 or not kept:
        raise SystemExit(f"the run gave no trace (exit {rc})")
    compiles = {p: runtime.backend_compiles(p) for p in PROGRAMS}
    texts = {}
    for s in spies:
        if s.lowered is not None:
            text = s.hlo_text()
            texts[text.split(None, 2)[1].rstrip(",")] = text
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    return line, kept[0], texts, compiles


def read_trace(path: str) -> dict:
    """Window, device op and module events, and every host event, in ns
    after the trace's start; ``start``, that start in realtime ns, the
    clock of ``repro.obs.runtime``'s stamps."""
    from jax.profiler import ProfileData
    from chipbench import trace as tr
    prof = ProfileData.from_file(tr.find_xplane(path))
    devices, host, start = [], [], None
    for plane in prof.planes:
        start = dict(plane.stats).get("profile_start_time", start)
        if plane.name.startswith(tr.DEVICE_PREFIX):
            lines = {line.name: [(e.start_ns, e.end_ns, e.name)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (tr.OPS_LINE, tr.MODULES_LINE)}
            if lines.get(tr.OPS_LINE):
                devices.append(lines)
        elif plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                host += [(e.start_ns, e.end_ns, e.name, line.name)
                         for e in line.events]
    window = next((a, b) for a, b, n, _ in host if n == tr.WINDOW_SPAN)
    return {"window": window, "devices": devices, "host": host,
            "start": start}


def layer_times(ev: dict, texts: dict) -> dict:
    """Own seconds per program, layer and pass over the window, averaged
    over the devices, the share that maps to a named layer, and each
    operation's own seconds with its layer."""
    from chipbench import trace as tr
    from repro.obs.scopes import OTHER, op_layers
    maps = {name: op_layers(text) for name, text in texts.items()}
    lo, hi = ev["window"]
    own: dict = collections.Counter()
    ops: dict = collections.Counter()
    for lines in ev["devices"]:
        mods = sorted((a, b, n.split("(")[0]) for a, b, n
                      in lines.get(tr.MODULES_LINE, []) if b > lo and a < hi)
        starts = [m[0] for m in mods]
        inside = []
        for a, b, hlo in lines[tr.OPS_LINE]:
            if b <= lo or a >= hi:
                continue
            k = bisect.bisect_right(starts, a) - 1
            module = mods[k][2] if k >= 0 and a < mods[k][1] else "none"
            instr = hlo.partition(" = ")[0].lstrip("%")
            inside.append((max(a, lo), min(b, hi), (module, instr)))
        for (module, instr), t in tr.self_times(inside).items():
            layer, way = maps.get(module, {}).get(instr, (OTHER, "fwd"))
            own[(module, layer, way)] += t
            ops[(module, instr, layer, way)] += t
    n_dev = max(len(ev["devices"]), 1)
    total = sum(own.values())
    named = sum(t for (_, layer, _), t in own.items() if layer != OTHER)
    rows = sorted(([m, layer, way, t / n_dev * 1e-9]
                   for (m, layer, way), t in own.items()),
                  key=lambda r: -r[3])
    return {"own_s": total / n_dev * 1e-9,
            "named_share": named / total if total else None,
            "rows": rows,
            "ops": sorted(([*k, t / n_dev * 1e-9] for k, t in ops.items()),
                          key=lambda r: -r[4])}


def gaps(ev: dict, top: int = 12) -> list:
    """Idle gaps of the device over ``GAP_MS`` in the window, each with the
    host events, compile spans and data waits that overlap it."""
    from chipbench import trace as tr
    from repro.obs import runtime
    lo, hi = ev["window"]
    t0 = ev["start"] or 0
    out = []
    for lines in ev["devices"][:1]:
        merged = tr.union(tr.clip([(a, b) for a, b, _ in
                                   lines[tr.OPS_LINE]], lo, hi))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if (b - a) * 1e-6 < GAP_MS:
                continue
            cover = sorted(((min(e, b) - max(s, a)) * 1e-6, name, thread,
                            (s - a) * 1e-6, (e - s) * 1e-6)
                           for s, e, name, thread in ev["host"]
                           if e > a and s < b)
            compiles = [[sp.name, sp.args.get("fun_name"),
                         (sp.t0 - t0 - a) * 1e-6, (sp.t1 - sp.t0) * 1e-6]
                        for sp in runtime.compiles.spans_of("compile")
                        if sp.t1 - t0 > a and sp.t0 - t0 < b]
            waits = [[c.name, (c.t - t0 - a) * 1e-6, c.value * 1e3]
                     for c in runtime.data.counters if a <= c.t - t0 <= b]
            out.append({"start_s": (a - lo) * 1e-9, "ms": (b - a) * 1e-6,
                        "host": [list(c) for c in cover[::-1][:top]],
                        "compiles": compiles, "data": waits})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", help="JSON report; the compiled texts of "
                    "the step programs are written beside it")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(REPO), str(REPO / "src")]
    line, tdir, texts, compiles = run_kept(["--workload", args.workload,
                                            "--seed", str(args.seed),
                                            "--seconds", str(args.seconds)])
    print(json.dumps(line), flush=True)
    ev = read_trace(tdir)
    report = {"workload": args.workload, "seed": args.seed, "line": line,
              "compiles": compiles, "layers": layer_times(ev, texts),
              "gaps": gaps(ev)}
    lt = report["layers"]
    print(f"device own time {lt['own_s']:.4f} s, named share "
          f"{lt['named_share']}, compiles {compiles}")
    for module, layer, way, t in lt["rows"]:
        print(f"  {module:18s} {layer:14s} {way} {t:10.4f} s")
    for g in report["gaps"]:
        print(f"gap at {g['start_s']:.3f} s, {g['ms']:.2f} ms: "
              f"compiles {g['compiles']}, data {g['data']}")
        for c in g["host"][:6]:
            print("   ", c)
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=1))
        for module, text in texts.items():
            out.with_suffix(f".{module}.hlo").write_text(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
