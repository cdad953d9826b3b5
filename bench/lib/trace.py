"""Read a JAX profiler trace into device operations and host spans.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it with JAX alone. Each ``/device:TPU:<n>`` plane holds an ``XLA Ops``
line (one event per executed HLO operation, named as in the compiled HLO)
and an ``XLA Modules`` line (one event per executed program, named after
the jitted function). Host planes hold the benchmark's own spans
(``jax.profiler.TraceAnnotation``, all named ``bench.*``), among them
``bench.trace_window``, which bounds the traced stretch of the window.
All times are nanoseconds on one clock.
"""
from __future__ import annotations

import glob
import os
import re

from bench.lib import reduce

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
WINDOW_SPAN = "bench.trace_window"
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
# a TPU trace names an operation by its HLO line: "%fusion.3 = f32[8]{0}
# fusion(...), kind=..."; keep the instruction's name and its opcode
_EVENT = re.compile(r'^%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(')


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event (the event's own name
    where it is not an HLO line)."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def op_label(event_name: str) -> str:
    m = _EVENT.match(event_name)
    return f"{m.group(1)} {m.group(2)}" if m else event_name


def scopes_from_hlo(hlo_text: str) -> dict:
    """HLO instruction name -> its ``op_name`` metadata (the JAX scope
    path, e.g. ``jit(train_step)/jit(_qdq_flat_impl)/...``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out.setdefault(m.group(1), m.group(2))
    return out


class DeviceTrace:
    """One chip's operations ``(name, start, end)`` and program runs."""

    def __init__(self, ops, modules, window):
        self.window = window
        self.ops = reduce.clip_named(ops, *window)
        self.modules = modules

    def intervals(self, pred) -> list:
        return [(s, e) for name, s, e in self.ops if pred(name)]

    def op_seconds(self, pred) -> float:
        """Seconds in which an operation matching ``pred`` ran (nested
        operations, as a loop's body in the loop, count once)."""
        return reduce.length(self.intervals(pred)) * 1e-9

    def module_seconds(self, part: str) -> float:
        """Device seconds of the runs of programs whose name holds
        ``part``, inside the window."""
        lo, hi = self.window
        return reduce.length(reduce.clip(
            [(s, e) for name, s, e in self.modules if part in name],
            lo, hi)) * 1e-9

    def module_count(self, part: str) -> float:
        """Runs of programs whose name holds ``part`` inside the window:
        their time in it over the median length of a run. (A run that was
        under way when the trace began is recorded cut short, so its own
        length cannot give its share.)"""
        lo, hi = self.window
        runs = [(s, e) for name, s, e in self.modules
                if part in name and e > s]
        whole = sorted(e - s for s, e in runs if lo <= s and e <= hi)
        inside = sum(max(0, min(e, hi) - max(s, lo)) for s, e in runs)
        if not inside:
            return 0.0
        if not whole:
            whole = sorted(e - s for s, e in runs)
        return inside / whole[len(whole) // 2]

    @staticmethod
    def is_collective(name: str) -> bool:
        return any(c in name for c in COLLECTIVES)

    @staticmethod
    def exposed_seconds(collectives, compute) -> float:
        return reduce.exposed(collectives, compute) * 1e-9

    def busy_ns(self) -> float:
        return reduce.busy([(s, e) for _, s, e in self.ops], *self.window)


class TraceData:
    def __init__(self, devices, host_spans, window, scopes=None):
        self.devices, self.host, self.window = devices, host_spans, window
        self.scopes = scopes or {}

    def scope(self, name: str) -> str:
        """The JAX scope path of the operation a device event names."""
        return self.scopes.get(name, name)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(d.busy_ns() for d in self.devices) * 1e-9 / len(
            self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def breakdown(self, k: int = 10) -> dict:
        """The ``k`` device operations (summed by label, chip 0) that took
        most time, and the ``k`` longest idle gaps of chip 0, each named
        by the host span that overlaps it most."""
        dev = self.devices[0]
        ops = reduce.top([(self.label(n), s, e) for n, s, e in dev.ops], k)
        gaps = reduce.name_gaps(
            reduce.gaps([(s, e) for _, s, e in dev.ops], *self.window),
            [h for h in self.host if h[0] != WINDOW_SPAN])[:k]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[n, t] for n, t in gaps]}

    def label(self, name: str) -> str:
        """A readable name for an operation: its HLO name and opcode, and
        the last two parts of its JAX scope path where that is known."""
        scope = self.scopes.get(name)
        return f"{op_label(name)} {'/'.join(scope.split('/')[-2:])}" \
            if scope else op_label(name)


def load(trace_dir: str, *, n_devices: int, hlo_text: str = "") -> TraceData:
    """Read the trace written under ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    per_device, host = {}, []
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(op_name(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    modules = [(ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events]
            per_device[int(m.group(1))] = (ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if not spans:
        raise RuntimeError("the trace holds no bench.trace_window span")
    window = (spans[0][1], spans[0][2])
    ids = sorted(per_device)[:n_devices]
    if not ids:
        raise RuntimeError("the trace holds no TPU device plane")
    devices = [DeviceTrace(*per_device[i], window) for i in ids]
    return TraceData(devices, host, window, scopes_from_hlo(hlo_text))
