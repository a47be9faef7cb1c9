"""Record the small device trace that ``test_chipbench_trace.py`` reduces.

    python tests/chipbench/record_trace.py <out_dir>

Run on the chip.  Three calls of one small jitted program, with host
sleeps between them, inside the harness spans ``window``, ``dispatch``,
``sync`` and ``data``; writes ``small.xplane.pb`` to ``out_dir`` and prints
every device operation and harness span, in ns on the trace's clock.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("data"):
                time.sleep(0.002)
            with TraceAnnotation("dispatch"):
                y = f(x)
            with TraceAnnotation("sync"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(src, dst)
    prof = ProfileData.from_file(dst)
    for plane in prof.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            keep = plane.name.startswith("/device:TPU:0") or any(
                e.name in ("window", "data", "dispatch", "sync") for e in evs)
            for e in evs if keep else evs[:2]:
                if keep and plane.name.startswith("/host") and e.name not in (
                        "window", "data", "dispatch", "sync"):
                    continue
                print("    ", repr(e.name), e.start_ns, e.end_ns)
    print("size", os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
