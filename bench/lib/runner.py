"""One run of one cell: devices, context, tracing, result line."""
from __future__ import annotations

import math
import shutil
import sys
import tempfile

from bench.lib import compare, trace as trace_lib
from bench.lib.peaks import peaks_for


def note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def accelerators(chips: int):
    """The first ``chips`` TPU devices, or None (after saying why) where
    JAX finds no TPU or too few. Never the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        note(f"JAX finds no devices: {e}")
        return None
    if devices[0].platform != "tpu":
        note(f"no TPU: JAX's devices are {devices}")
        return None
    if len(devices) < chips:
        note(f"the cell needs {chips} chips, JAX finds {len(devices)}")
        return None
    return devices[:chips]


class Tracer:
    """Profiles one stretch of the window: from ``start_at`` seconds after
    it opens, for ``length`` seconds (or until ``stop``). ``tick`` is
    called between steps, so that no step is cut by the trace's edges."""

    def __init__(self, enabled: bool, start_at: float, length: float):
        self.enabled, self.start_at, self.length = enabled, start_at, length
        self.active = False
        self.done = False
        self.dir = None
        self._ann = None

    def tick(self, now: float) -> None:
        if not self.enabled or self.done:
            return
        if not self.active and now >= self.start_at:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation("bench.trace_window")
            self._ann.__enter__()
            self.active = True
        elif self.active and now >= self.start_at + self.length:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active, self.done = False, True


class Ctx:
    """What a cell's ``run`` gets: the cell's files and model family, the
    run's arguments, the devices, and a way to report."""

    def __init__(self, cell, *, seed, seconds, trace, devices, t_process):
        self.workload = cell.name
        self.config, self.mix, self.limits = cell.config, cell.traffic, \
            cell.limits
        self.family = cell.family()
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.t_process = devices, t_process
        self._tracer = None
        self.in_window = False
        self.compiles_in_window = 0
        self._last_mark = t_process
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def mark(self, what: str) -> None:
        """Say how long the set-up step ``what`` took since the last."""
        import time
        now = time.perf_counter()
        note(f"set-up: {what} {now - self._last_mark:.3f} s")
        self._last_mark = now

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.in_window and event.endswith("backend_compile_duration"):
            self.compiles_in_window += 1

    note = staticmethod(note)

    def tracer(self) -> Tracer:
        span = float(self.mix["trace_seconds"])
        self._tracer = Tracer(self.trace, max(0.0, (self.seconds - span) / 2),
                              span)
        return self._tracer

    def memory_peak(self) -> int:
        """Peak bytes on the fullest chip. The TPU runtime holds a
        program's temporaries in reserved memory, which
        ``peak_bytes_in_use`` leaves out, so both are counted."""
        return max(sum(int((d.memory_stats() or {}).get(k, 0)) for k in (
            "peak_bytes_in_use", "peak_bytes_reserved"))
            for d in self.devices)


class Reading:
    """What a per-layer metric's ``read`` gets."""

    def __init__(self, trace, facts, peaks, config, mix, family):
        self.trace, self.facts, self.peaks = trace, facts, peaks
        self.config, self.mix, self.family = config, mix, family


def device_info(devices, memory_peak) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}


def run_cell(cell, *, seed, seconds, trace, devices, t_process) -> dict:
    from bench.lib import program
    note(f"compile cache: {program.use_compile_cache()}")
    ctx = Ctx(cell, seed=seed, seconds=seconds, trace=trace,
              devices=devices, t_process=t_process)
    peaks = peaks_for(devices[0].device_kind)
    out = cell.driver().run(ctx)
    note(f"programs compiled inside the window: {ctx.compiles_in_window}")
    checks = out["checks"]
    correct = compare.passed(checks) and out["failed"] == 0 \
        and out["attempted"] > 0
    device = device_info(devices, out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        tr = ctx._tracer
        data = trace_lib.load(tr.dir, n_devices=len(devices),
                              hlo_text=out["facts"].get("hlo_text", ""))
        shutil.rmtree(tr.dir, ignore_errors=True)
        reading = Reading(data, out["facts"], peaks, cell.config, cell.traffic,
                          ctx.family)
        metrics = {}
        readers = cell.metric_readers()
        for m in cell.per_layer:
            value = readers[m["name"]].read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = data.busy_s()
        device["window_s"] = data.window_s()
        result["breakdown"] = data.breakdown()
    else:
        values = dict(out["metrics"], setup_s=out["setup_s"])
        metrics = {m["name"]: {"value": _num(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update({"metrics": metrics, "device": device})
    note(f"correct={correct} attempted={out['attempted']} "
         f"failed={out['failed']}")
    for name, value, limit in checks:
        note(f"check {name} = {value!r} limit {limit!r} "
             f"{'ok' if value <= limit else 'FAIL'}")
    result["checks"] = {name: {"value": _num(value), "limit": limit}
                        for name, value, limit in checks}
    return result


def _num(x: float):
    """A number as JSON can hold it: an infinite one (a miss) as text."""
    return x if math.isfinite(x) else str(x)

