"""Record the small profiler trace that ``tests/bench/test_trace.py``
reduces, and print the trace's planes and lines.

    python3 bench/record_testdata.py chiprun_out/trace_small.xplane.pb

On the chip: a ``bench.window`` annotation around five steps, each a
``bench.host`` span (the host sleeps 20 ms, the device idles) followed
by a ``bench.dispatch`` span (a 1024x1024 f32 matmul, waited for).
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from bench import trace

    out = Path(sys.argv[1])
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(log_dir)
    with TraceAnnotation("bench.window"):
        for _ in range(5):
            with TraceAnnotation("bench.host"):
                time.sleep(0.02)
            with TraceAnnotation("bench.dispatch"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(log_dir)
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(log_dir, ignore_errors=True)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(out)).planes:
        lines = [(line.name, len(list(line.events))) for line in plane.lines]
        print("plane", plane.name, lines[:12])
        for line in plane.lines:
            evs = list(line.events)[:3]
            for e in evs:
                print("   ", line.name, "|", e.name[:60], e.start_ns,
                      e.duration_ns)
    print("reduced", trace.reduce(trace.read_xplane(str(out))))
    print("bytes", out.stat().st_size)


if __name__ == "__main__":
    main()
