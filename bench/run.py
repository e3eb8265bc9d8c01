"""Benchmark harness: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload region1024.steady --seed 7 \
        --seconds 10 --trace 0

The cell names a configuration and a traffic mix; everything else is
found by name, so a later change adds a cell by adding files:

- ``bench/configs/<config>.json``: the deployment, with its ``driver``;
- ``bench/mixes/<traffic>.json``: the traffic mix, data for
  ``bench/traffic.py``;
- ``bench/drivers/<driver>.py``: set-up, the measured window and the
  correctness check of one served path;
- ``bench/metrics/<metric>.py``: ``read(run)`` of one metric from what
  the run recorded (``None`` where there is nothing to read).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window. The last line of
standard output is one JSON object; the numbers compared for
``correct`` are the last lines of standard error and the last key of
that object. Without a TPU (or with fewer chips than the cell asks
for) the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: jax.monitoring events that mean a program was traced or compiled.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for: no result."""


# ------------------------------------------------------------ discovery
def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def find_cell(root: Path, name: str) -> Tuple[dict, dict, dict, dict]:
    """(benchmark, cell, config, mix) of cell ``name``, from files."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    mix = load_json(root / "bench" / "mixes" / f"{cell['traffic']}.json")
    return bench, cell, config, mix


def load_module(root: Path, kind: str, name: str):
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports in this mode."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------------ run
@dataclasses.dataclass
class Run:
    """What one run recorded; drivers fill it, metric readers read it."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float = T_PROCESS
    setup_s: Optional[float] = None
    window_s: Optional[float] = None
    #: host-clock spans by layer name: (start, end) in perf_counter s
    spans: Dict[str, List[Tuple[float, float]]] = dataclasses.field(
        default_factory=dict)
    counts: Dict[str, float] = dataclasses.field(default_factory=dict)
    values: Dict[str, object] = dataclasses.field(default_factory=dict)
    #: numbers compared for ``correct``: name -> (value, limit)
    checks: Dict[str, Tuple[float, float]] = dataclasses.field(
        default_factory=dict)
    attempted: int = 0
    failed: int = 0
    trace_summary: Optional[dict] = None
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    _annotate: bool = False

    @contextlib.contextmanager
    def span(self, name: str):
        """Host-clock span of one call into a layer; in a traced run
        also a ``bench.<name>`` annotation on the profiler's clock."""
        ann = contextlib.nullcontext()
        if self._annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens, and in a
        traced run the profiler records exactly this interval."""
        import jax

        compiles = [0]

        def listen(event, *_a, **_k):
            if event in COMPILE_EVENTS:
                compiles[0] += 1

        log_dir = None
        if self.trace:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(log_dir)
            self._annotate = True
        jax.monitoring.register_event_duration_secs_listener(listen)
        ann = (jax.profiler.TraceAnnotation("bench.window")
               if self.trace else contextlib.nullcontext())
        try:
            with ann:
                t0 = time.perf_counter()
                self.setup_s = t0 - self.t_process
                start = compiles[0]
                yield t0
                self.compiles_in_window = compiles[0] - start
                self.memory_peak_bytes = memory_peak()
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
            if log_dir is not None:
                jax.profiler.stop_trace()
                self._annotate = False
                try:
                    from bench import trace as bench_trace

                    self.trace_summary = bench_trace.reduce(
                        bench_trace.read_xplane(
                            bench_trace.find_xplane(log_dir)))
                finally:
                    shutil.rmtree(log_dir, ignore_errors=True)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v <= lim for v, lim in self.checks.values())


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``.jax_cache`` at the root of the checkout (a fixed path, so
    later runs find what the first compiled). Every program is cached,
    however fast it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(chips: int) -> dict:
    info = device_info()
    if info["platform"] != "tpu":
        raise NoChip(f"bench: no TPU (JAX found {info['platform']}); "
                     "nothing was measured")
    if info["count"] < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{info['count']}")
    return info


def run_cell(root: Path, bench: dict, cell: dict, config: dict, mix: dict,
             *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True) -> Tuple[dict, Run]:
    """One run; returns the result object (the last line) and what the
    run recorded."""
    device = (require_chips(cell["chips"]) if require_tpu
              else device_info())
    run = Run(cell=cell, config=config, mix=mix, seed=seed,
              seconds=seconds, trace=trace)
    driver = load_module(root, "drivers", config["driver"])
    t_driver = time.perf_counter()
    driver.run(run)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = load_module(root, "metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        out["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
    print(f"[bench] {cell['name']} seed={seed}: set-up "
          f"{run.setup_s:.3f} s, compilations in the window "
          f"{run.compiles_in_window}", file=sys.stderr, flush=True)
    phases = [("start", t_driver - run.t_process)] + [
        (k[len("setup."):], sum(b - a for a, b in v))
        for k, v in run.spans.items() if k.startswith("setup.")]
    print("[bench] set-up by phase (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases), file=sys.stderr, flush=True)
    if run.trace_summary is not None:
        s = run.trace_summary
        print("[bench] longest idle gaps (label, s, start in window): "
              + ", ".join(f"{g[0]} {g[1]:.3f} @{o:.3f}" for g, o in
                          zip(s["idle_gaps"][:3], s["idle_gap_offsets"])),
              file=sys.stderr, flush=True)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    for k, (v, lim) in run.checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr,
              flush=True)
    return out, run


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, mix = find_cell(ROOT, args.workload)
    enable_compile_cache(ROOT)
    out, _ = run_cell(ROOT, bench, cell, config, mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
