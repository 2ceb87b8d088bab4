"""What every cell shares: finding files by name, the device gate, the
compile cache, counting compilations, tracing, and the result line.

Nothing here names a configuration, a traffic mix, a metric or a cell.
``BENCHMARK.json`` names them; their files are found by listing
``benchmark/configs``, ``benchmark/traffic``, ``benchmark/metrics`` and
``benchmark/limits``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NO_DEVICE = 3  # exit code: no accelerator, or fewer chips than the cell asks


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(name: str) -> dict:
    for w in manifest()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(os.path.join(BENCH, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH, "traffic", f"{name}.json"))


def limits(cell: str) -> dict:
    return _json(os.path.join(BENCH, "limits", f"{cell}.json"))


def metric_reader(name: str):
    """The ``read(run)`` of ``benchmark/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_for(cell: str, section: str) -> list[dict]:
    """The manifest's metrics of ``section`` that this cell reports."""
    return [
        m for m in manifest()[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


# -- the device --------------------------------------------------------------


def require_chips(chips: int):
    """The TPU devices of this process, or exit without a result line."""
    import jax

    from benchmark.lib import peaks

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        raise SystemExit(NO_DEVICE) from None
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"need {chips} TPU chip(s), found {len(devices)} x "
            f"{devices[0].platform}", file=sys.stderr)
        raise SystemExit(NO_DEVICE)
    try:
        peak = peaks.peaks_for(devices[0].device_kind)
    except peaks.UnknownDevice as e:
        print(str(e), file=sys.stderr)
        raise SystemExit(NO_DEVICE) from None
    return devices[:chips], peak


def configure_cache() -> str:
    """JAX's persistent compilation cache: where the environment says,
    else ``<checkout>/.jax_cache`` (a fixed path: it is part of the key).
    Every program is cached, however quick its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip, read while the cell's state is alive. On
    this backend ``peak_bytes_in_use`` counts live buffers only; a running
    program's temporaries are the allocator's *reserved* bytes, on top of
    them. So: the live buffers now plus the largest reservation so far, or
    the largest live-buffer reading if that is higher (set-up may hold
    more than the window does, but not while the step's program runs)."""
    best = 0
    for d in devices:
        s = d.memory_stats() or {}
        best = max(
            best, int(s.get("peak_bytes_in_use", 0)),
            int(s.get("bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0)))
    return best


class Marks:
    """Where a run's time went, by part; printed on standard error."""

    def __init__(self, t0: float):
        self.last = t0
        self.parts: list[tuple[str, float]] = []

    def __call__(self, name: str) -> None:
        now = time.perf_counter()
        self.parts.append((name, now - self.last))
        self.last = now

    def report(self) -> None:
        print("parts_s " + " ".join(f"{n}={s:.2f}" for n, s in self.parts),
              file=sys.stderr)


class CompileCounter:
    """Counts programs lowered (every new shape lowers, cached or not)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


# -- tracing -----------------------------------------------------------------


class Tracer:
    """Profiler trace of the last ``seconds`` of the window. The cell's
    driver calls :meth:`maybe_start` at its dispatch boundaries and
    :meth:`stop` once the window has closed: stopping a trace takes the
    profiler many seconds, in which nothing else runs, so it may not
    happen inside the window. With ``enabled`` false every call is a
    no-op."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.seconds = seconds
        self.dir = os.path.join(ROOT, ".bench_trace")
        self.state = "idle"  # idle -> on -> done

    def maybe_start(self, elapsed_s: float, window_s: float) -> bool:
        """Start once the window's last ``seconds`` begin; True when this
        call started the trace."""
        if (not self.enabled or self.state != "idle"
                or elapsed_s < window_s - self.seconds):
            return False
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.state = "on"
        return True

    def stop(self) -> None:
        if self.state == "on":
            import jax

            jax.profiler.stop_trace()
            self.state = "done"

    def annotate(self, name: str):
        if self.state != "on":
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def summary(self, chips: int):
        """The reduced trace, then the files are deleted."""
        from benchmark.lib import trace

        self.stop()
        if self.state != "done":
            return None
        path = sorted(glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        try:
            planes = trace.load(path)
            if os.environ.get("BENCH_TRACE_FIXTURE"):  # to record a test fixture
                trace.dump_fixture(planes, os.environ["BENCH_TRACE_FIXTURE"])
            return trace.summarize(planes, chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- one run's record and its result line --------------------------------------


@dataclasses.dataclass
class Run:
    """What a cell's driver hands to the metric readers."""

    cell: str
    cfg: dict
    traffic: dict
    chips: int
    peaks: dict
    window_s: float = 0.0
    end_to_end: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    requests: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)
    trace: object = None
    memory_peak_bytes: int = 0
    compiles_in_window: int = 0
    attempted: int = 0
    failed: int = 0
    checks: dict = dataclasses.field(default_factory=dict)  # name -> (value, limit)


def judge(checks: dict) -> bool:
    """``correct``: every number compared is finite and within its limit."""
    return bool(checks) and all(
        math.isfinite(v) and v <= lim for v, lim in checks.values())


def result_line(run: Run, devices, traced: bool) -> dict:
    section = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in metrics_for(run.cell, section):
        if traced:
            value = metric_reader(m["name"])(run)
        else:
            value = run.end_to_end.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(run.memory_peak_bytes),
    }
    line = {
        "correct": judge(run.checks),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    if traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {
            "device_ops": run.trace.top_ops(10),
            "idle_gaps": run.trace.top_gaps(10),
        }
    line["checks"] = {
        k: {"value": float(v), "limit": float(lim)}
        for k, (v, lim) in run.checks.items()
    }
    return line


def emit(run: Run, devices, traced: bool) -> int:
    line = result_line(run, devices, traced)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
