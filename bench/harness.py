"""One run of one cell: device check, set-up, window, check, result line.

Everything specific to a configuration, traffic mix or metric is found by
the name ``BENCHMARK.json`` gives it (see the package docstring); nothing
here changes when a cell, mix or metric is added.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".trace")

__all__ = ["NoChip", "load_spec", "resolve", "load_peaks", "check_device",
           "metric_reader", "metrics_for", "run_cell", "winner_key",
           "result_line", "print_checks"]


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip the peaks table lacks."""


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> Dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def resolve(spec: Dict, workload: str):
    """-> (cell, config, traffic) for the cell named ``workload``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = _read_json(os.path.join(ROOT, entry["file"]))
    traffic = _read_json(os.path.join(HERE, "traffic",
                                      f"{cell['traffic']}.json"))
    return cell, config, traffic


def load_peaks() -> Dict:
    return _read_json(os.path.join(HERE, "peaks.json"))


def check_device(chips: int, peaks: Dict) -> Dict:
    """The chips this run may use; raises :class:`NoChip` unless JAX sees
    at least ``chips`` TPU devices of a kind the peaks table holds."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {d0.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    if d0.device_kind not in peaks["kinds"]:
        raise NoChip(f"device kind {d0.device_kind!r} is not in "
                     f"bench/peaks.json ({sorted(peaks['kinds'])})")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def metric_reader(name: str) -> Callable[[Dict], Optional[float]]:
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(spec: Dict, workload: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer ones."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


# ------------------------------------------------------ compile accounting
class CompileWatch:
    """Counts JAX's tracing, compiling and persistent-cache events, so that
    a run can show that nothing compiled inside its window."""

    _EVENTS = {
        "/jax/compilation_cache/cache_misses": "cache_misses",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/core/compile/backend_compile_duration": "backend_compiles",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
    }
    _instance = None

    def __init__(self):
        import jax.monitoring as mon
        self.counts = {v: 0 for v in self._EVENTS.values()}
        mon.register_event_listener(self._on)
        mon.register_event_duration_secs_listener(
            lambda event, _secs, **_kw: self._on(event))

    @classmethod
    def get(cls) -> "CompileWatch":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, event: str, **_kw) -> None:
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


def _memory_peak(n: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices()[:n]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _say(*args) -> None:
    print(*args, flush=True)


# ------------------------------------------------------------------ a run
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, spec: Optional[Dict] = None,
             cell=None, config=None, traffic=None,
             device: Optional[Dict] = None, log=_say) -> Dict:
    """Set up, warm, measure ``seconds``, check; returns the result object.

    Without ``device`` the run checks for the cell's chips and turns on the
    persistent compile cache; tests pass a description of the CPU instead,
    which is the only way past the chip check."""
    import jax
    from repro.kernels import tuning

    spec = spec or load_spec()
    if cell is None:
        cell, config, traffic = resolve(spec, workload)
    peaks_table = load_peaks()
    if device is None:
        from repro.compile_cache import enable_compile_cache
        device = check_device(cell["chips"], peaks_table)
        log(f"compile cache {enable_compile_cache()}")
    peaks = peaks_table["kinds"].get(device["kind"])
    log(f"device {device}; jax {jax.__version__}; pallas interpret "
        f"{tuning.resolve_interpret()}")
    watch = CompileWatch.get()
    system = importlib.import_module(f"bench.systems.{config['system']}")
    problem = system.build(config, traffic, seed, log)

    before = watch.snapshot()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        samples = problem.window(float(seconds))
    summary = None
    if trace:
        jax.profiler.stop_trace()
    window_s = samples["t1"] - samples["t0"]
    setup_s = samples["t0"] - t_start
    inside = {k: v - before[k] for k, v in watch.snapshot().items()}
    log(f"window {window_s:.6f} s after set-up {setup_s:.6f} s; "
        f"compile events inside the window {inside}")
    log(f"work counted: {json.dumps(samples.get('counts', {}))}")
    log(f"autotune stats {tuning.stats()}")
    winners = tuning.winners()
    for key, name in sorted(winners.items(), key=str):
        log(f"autotune winner {key} -> {name}")
    mem = _memory_peak(cell["chips"])
    log(f"device memory peak {mem} bytes")
    if trace:
        from bench.trace import find_xplane, reduce_trace
        t = time.perf_counter()
        summary = reduce_trace(find_xplane(TRACE_DIR))
        log(f"trace reduced in {time.perf_counter() - t:.6f} s: "
            f"busy {summary['busy_s']:.6f} s of {summary['window_s']:.6f} s "
            f"on {summary['devices']} device(s)")

    readings = problem.readings(with_control=False)["program"]
    del problem
    gc.collect()
    log(f"readings {json.dumps(readings)}")
    # a configuration states limits for each of its drivers' numbers
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in config["limits"].items() if k in readings}
    if not checks:
        raise ValueError(f"no limit in the configuration for {sorted(readings)}")
    failed = sum(not (c["value"] <= c["limit"]) for c in checks.values())

    ctx = {"samples": samples, "window_s": window_s, "setup_s": setup_s,
           "peaks": peaks, "trace": summary}
    metrics = {}
    for m in metrics_for(spec, workload, trace):
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=mem)
    if summary is not None:
        dev["busy_s"], dev["window_s"] = summary["busy_s"], summary["window_s"]
    result = {"correct": failed == 0,
              "attempted": int(samples["attempted"]), "failed": failed,
              "metrics": metrics, "device": dev}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    # this process's sweep winners, so a run slowed by a flipped winner
    # shows it on its own line
    shown = {}
    for key, name in sorted(winners.items(), key=str):
        short = winner_key(key)
        while short in shown:
            short += "'"
        shown[short] = name
    result["autotune_winners"] = shown
    result["checks"] = checks
    return result


def winner_key(key) -> str:
    """A sweep's signature in short: its kind, the two row counts, the
    unit's shape and the dtype (the head of the autotuner's key)."""
    if not isinstance(key, tuple):
        return str(key)
    return " ".join(str(getattr(x, "name", x)) for x in key[:5])


def result_line(result: Dict) -> str:
    """The result object as its one JSON line.  JSON has no NaN or
    infinity: a metric that is not finite is refused, and a compared number
    that is not finite is written as the largest float (over any limit)."""
    for m in result["metrics"].values():
        if not math.isfinite(m["value"]):
            raise ValueError(f"metric value {m['value']!r} is not finite")
    for c in result["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = sys.float_info.max
    return json.dumps(result, allow_nan=False)


def print_checks(result: Dict) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
