"""The train step's phases, read from the names the program gives them.

``repro.train.steps`` wraps each phase of its train step in a
``jax.named_scope``, and every compiled operation carries its scope path
in the HLO's ``op_name`` metadata (``trace.scopes_from_hlo``). A fusion
carries the scope of its root operation. Each device operation goes to one
class, tested in this order:

1. ``exchange``: a collective (by its HLO opcode, or by its name);
2. ``codec``: under ``_qdq_flat_impl``, the gradient codec's kernels;
3. the phase of its scope: ``forward`` (``train.forward`` outside any
   ``transpose(``), ``backward`` (``transpose(`` of ``train.forward``),
   ``grad_prep`` (``train.clip``, ``train.flatten``,
   ``train.error_feedback``, ``train.unflatten``), ``optimizer``
   (``train.optimizer``).

An operation the compiler added without a scope (a layout copy, an
asynchronous slice or copy between memory spaces, the concatenation of
their parts) takes the class of the first operation that uses its
result, else of the first it reads: XLA adds it to serve that operation.
Anything else (``train.codec`` outside the kernels: the key's
``fold_in``) is in no class. A class's time per step is the union of its
operations' intervals over the steps in the window
(``module_count("train_step")``), averaged over the chips.

    python3 -m bench.lib.phases --workload <cell> --seed <n> --seconds <s>

makes one traced run, prints its result line as ``bench/run.py`` does,
and writes each chip's split to stderr: every class, the rest, and the
share of the busy time the classes cover.
"""
from __future__ import annotations

import re

from bench.lib import reduce
from bench.lib.trace import COLLECTIVES, DeviceTrace

CODEC_SCOPE = "_qdq_flat_impl"
GRAD_PREP_SCOPES = ("train.clip", "train.flatten", "train.error_feedback",
                    "train.unflatten")
CLASSES = ("forward", "backward", "grad_prep", "optimizer", "codec",
           "exchange")
STEP_MODULE = "train_step"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s"
                    r"([a-z][\w\-]*)\(([^)]*)\)(.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
# a scope path's part without the transformations around it:
# "transpose(jvp(train.forward))" -> "train.forward"
_BARE = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")


class Hlo:
    """The compiled program's instructions: opcode, operands, users and
    the instruction whose ``calls=`` runs each computation."""

    def __init__(self, hlo_text: str):
        self.opcode, self.operands, self.users = {}, {}, {}
        self.order, self.computation, self.caller = [], {}, {}
        comp = None
        for line in hlo_text.splitlines():
            if not line.startswith(" "):
                m = _COMPUTATION.match(line)
                comp = m.group(1) if m else None
                continue
            m = _INSTR.match(line)
            if not m or m.group(1) in self.opcode:
                continue
            name = m.group(1)
            self.opcode[name] = m.group(2)
            self.operands[name] = re.findall(r"%([\w.\-]+)", m.group(3))
            self.order.append(name)
            self.computation[name] = comp
            for callee in re.findall(r"calls=%?([\w.\-]+)", m.group(4)):
                self.caller.setdefault(callee, name)
        for name in self.order:
            for arg in self.operands[name]:
                self.users.setdefault(arg, []).append(name)


def classify(name: str, scope: str, opcode: str = "") -> str | None:
    """The class of the operation ``name`` with scope path ``scope``."""
    if DeviceTrace.is_collective(name) or opcode.startswith(COLLECTIVES):
        return "exchange"
    if CODEC_SCOPE in scope:
        return "codec"
    parts = {m.group(1) for m in map(_BARE.match, scope.split("/")) if m}
    if "train.forward" in parts:
        return "backward" if "transpose(" in scope else "forward"
    if parts.intersection(GRAD_PREP_SCOPES):
        return "grad_prep"
    if "train.optimizer" in parts:
        return "optimizer"
    return None


def hlo_classes(scopes: dict, hlo_text: str) -> dict:
    """HLO instruction name -> class. An instruction with a scope path is
    classed by it. One the compiler added without a scope (a layout copy,
    an asynchronous slice or copy between memory spaces, the
    concatenation of their parts) takes the class of the first
    instruction that uses its result, else of the first it reads; one
    inside a computation an instruction calls takes its caller's."""
    hlo = Hlo(hlo_text)
    own = {n: classify(n, scopes.get(n, n), hlo.opcode[n])
           for n in hlo.order}

    def bare(n):
        return n not in scopes and own[n] is None

    def via(n, table):
        return table.get(n) if bare(n) else own[n]

    down, up = {}, {}
    for n in reversed(hlo.order):
        down[n] = next(filter(None, (via(u, down)
                                     for u in hlo.users.get(n, ()))), None)
    for n in hlo.order:
        up[n] = next(filter(None, (via(a, up) for a in hlo.operands[n]
                                   if a in own)), None)
    out = {}
    for n in hlo.order:
        out[n] = own[n] if not bare(n) else (down[n] or up[n])
    for n in hlo.order:
        caller = hlo.caller.get(hlo.computation[n])
        if bare(n) and out[n] is None and caller is not None:
            out[n] = out.get(caller)
    return out


def op_classes(trace, hlo_text: str) -> dict:
    """Device operation name -> class (or None), for every operation of
    the trace."""
    known = hlo_classes(trace.scopes, hlo_text)
    out = {}
    for dev in trace.devices:
        for name, _, _ in dev.ops:
            if name not in out:
                out[name] = known[name] if name in known else classify(
                    name, trace.scope(name))
    return out


def seconds_per_step(trace, hlo_text: str, cls: str) -> float | None:
    """Device seconds per train step of the class ``cls``, averaged over
    the chips that ran it; None without steps or without such
    operations."""
    kinds = op_classes(trace, hlo_text)
    per_chip = []
    for dev in trace.devices:
        steps = dev.module_count(STEP_MODULE)
        t = dev.op_seconds(lambda op: kinds[op] == cls)
        if steps > 0 and t > 0:
            per_chip.append(t / steps)
    return sum(per_chip) / len(per_chip) if per_chip else None


def ms_per_step(r, cls: str) -> float | None:
    """``seconds_per_step`` of a reading, in ms."""
    s = seconds_per_step(r.trace, r.facts.get("hlo_text", ""), cls)
    return None if s is None else 1e3 * s


def split(trace, hlo_text: str, k: int = 5) -> list:
    """Per chip: ms per step of every class, of the busy time and of the
    rest no class covers, the share of the busy time the classes cover,
    and the ``k`` costliest operations of the rest."""
    kinds = op_classes(trace, hlo_text)
    out = []
    for dev in trace.devices:
        steps = dev.module_count(STEP_MODULE)
        if steps <= 0:
            out.append(None)
            continue
        row = {c: 1e3 * dev.op_seconds(lambda op, c=c: kinds[op] == c)
               / steps for c in CLASSES}
        busy = dev.busy_ns()
        covered = reduce.length(dev.intervals(lambda op: kinds[op]
                                              is not None))
        rest = reduce.top([(trace.label(n), s, e) for n, s, e in dev.ops
                           if kinds[n] is None], k)
        row.update(busy=1e-6 * busy / steps,
                   rest=1e-6 * (busy - covered) / steps,
                   covered=covered / busy if busy else 0.0, steps=steps,
                   rest_top=[[n, 1e3 * t / steps] for n, t in rest])
        out.append(row)
    return out


def main(argv=None) -> int:
    """One traced run of a cell; its result line on stdout, each chip's
    split on stderr. The harness deletes its trace once read, so the
    split is taken from the trace as ``runner`` loads it."""
    import argparse
    import json
    import time

    from bench.lib import runner, trace as trace_lib
    from bench.lib.spec import Cell

    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    devices = runner.accelerators(cell.chips)
    if devices is None:
        return 2
    splits = []
    load = trace_lib.load

    def load_and_split(trace_dir, *, n_devices, hlo_text=""):
        data = load(trace_dir, n_devices=n_devices, hlo_text=hlo_text)
        splits.extend(split(data, hlo_text))
        return data

    trace_lib.load = load_and_split
    try:
        result = runner.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                 trace=True, devices=devices,
                                 t_process=t_process)
    finally:
        trace_lib.load = load
    for chip, row in enumerate(splits):
        runner.note(f"phase split, chip {chip} (ms per step): "
                    f"{json.dumps(row)}")
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
