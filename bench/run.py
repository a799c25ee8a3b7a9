"""The benchmark: one cell of BENCHMARK.json, one run, one result line.

    python3 bench/run.py --workload cfm_forces_t3 --seed 7 --seconds 30 --trace 0

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``, whose ``driver`` names the
code under ``bench/drivers/`` that runs it); its limits for ``correct`` are
in ``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new cell, configuration, traffic mix or
metric is new files and entries, never an edit of these.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window under the profiler and reports its per-layer metrics.  The run
needs the chips the cell asks for: without them it prints no result and
exits 2.  The last line of standard output is the result as JSON; the last
lines of standard error give each compared number beside its limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"
EXIT_NO_CHIP = 2


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload with the configuration, traffic and limits it names, and
    the metrics it reports."""

    def __init__(self, name, chips, config, traffic, limits, end_to_end,
                 per_layer):
        self.name, self.chips = name, int(chips)
        self.config, self.traffic, self.limits = config, traffic, limits
        self.end_to_end, self.per_layer = end_to_end, per_layer

    @staticmethod
    def of_benchmark(bench: dict, name: str) -> "Cell":
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
        entry = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        return Cell(
            name, entry["chips"],
            read_json(ROOT / configs[entry["config"]]["file"]),
            read_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
            read_json(BENCH / "limits" / f"{name}.json")["limits"],
            [m for m in bench["end_to_end"] if _listed(m, name)],
            [m for m in bench["per_layer"] if _listed(m, name)])


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Run:
    """What a driver is given: the cell's files, the arguments, the chips,
    and the hooks that trace the window."""

    def __init__(self, cell: Cell, args, devices):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.limits = cell.limits
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.devices = devices
        self.t_process = T_PROCESS
        self.trace_dir = CACHE / "trace" / cell.name

    def annotate(self, trainer) -> dict:
        """Wrap the trainer's host steps in named spans, on the instance:
        the profiler sees them, and their host seconds are kept."""
        from jax.profiler import TraceAnnotation

        spans = {"missing": []}
        targets = [("bench.fetch_batch", trainer, "_fetch_batch"),
                   ("bench.engine_step", getattr(trainer, "engine", None), "step"),
                   ("bench.ema_update", getattr(trainer, "ema", None), "update")]
        for name, obj, attr in targets:
            fn = getattr(obj, attr, None)
            if fn is None:
                spans["missing"].append(name)
                continue
            spans[name] = []

            def wrapped(*a, _fn=fn, _name=name, **k):
                t0 = time.perf_counter()
                with TraceAnnotation(_name):
                    out = _fn(*a, **k)
                spans[_name].append(time.perf_counter() - t0)
                return out

            setattr(obj, attr, wrapped)
        return spans

    @contextlib.contextmanager
    def window_trace(self):
        if not self.trace:
            yield
            return
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        try:
            with TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()


def per_layer(cell: Cell, out: dict, run: Run, peaks: dict):
    """Reduce the window's trace and read each per-layer metric."""
    from benchlib import trace as tr
    from benchlib import work

    path = tr.find_xplane(str(run.trace_dir))
    trace = tr.load(path)
    ctx = {"trace": trace, "steps": out["steps"], "sizes": out["sizes"],
           "slots": out["slots"], "peaks": peaks, "chips": cell.chips,
           "host_spans": out["host_spans"], "work": work}
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": tr.mean_busy_s(trace), "window_s": trace.window_s}
    longest = tr.longest_gaps(trace, 3)
    print("note: longest idle gaps (s from the window's start, s long): "
          + ", ".join(f"{a:.3f} {g:.4f}" for a, g in longest), file=sys.stderr)
    breakdown = {"device_ops": [[n, s] for n, s in tr.top_ops(trace)],
                 "idle_gaps": [[n, s] for n, s in tr.idle_gaps(trace)]}
    return metrics, device, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell.of_benchmark(read_json(ROOT / "BENCHMARK.json"), args.workload)
    jax = start_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"no chip: JAX gives {len(devices)} {devices[0].platform} "
              f"device(s), the cell needs {cell.chips} TPU", file=sys.stderr)
        return EXIT_NO_CHIP
    from benchlib.peaks import peaks_for

    devices = devices[:cell.chips]
    line = execute(cell, args, devices, peaks_for(devices[0].device_kind))
    print(json.dumps(line))
    return 0


def start_jax():
    """Import JAX with its compile cache (every program, however quick to
    compile) and the runtime's logs at fixed paths inside the checkout; a
    compile cache the environment names is used instead."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE / "jax"))
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    setup_paths()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def setup_paths() -> None:
    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def execute(cell: Cell, args, devices, peaks) -> dict:
    """Run the cell on ``devices`` and return its result line."""
    run = Run(cell, args, devices)
    driver = load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py",
                         f"bench_driver_{cell.traffic['driver']}")
    out = driver.run(run)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {}
    if args.trace:
        metrics, dev, breakdown = per_layer(cell, out, run, peaks)
        device.update(dev)
        result["breakdown"] = breakdown
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = out["checks"]
    correct = bool(checks) and all(c["ok"] for c in checks)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})"
              f"{'' if c['ok'] else ' FAILED'}", file=sys.stderr)
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            **result,
            "checks": {c["name"]: {"value": _num(c["value"]),
                                   "limit": c["limit"]} for c in checks}}
    return line


def _num(x: float):
    return x if math.isfinite(x) else str(x)


if __name__ == "__main__":
    sys.exit(main())
