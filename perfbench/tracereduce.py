"""Reduction of a profiler trace to what the per-layer readers need.

A traced run brackets its traced interval with a host span named
``WINDOW_SPAN``.  From the TPU planes (``/device:TPU:<n>``) the reduction
keeps, inside that interval:

* the ``XLA Modules`` line: one event per execution of a jitted program,
  named ``jit_<function>(<hash>)``;
* the ``XLA Ops`` line: one event per HLO operation.  A Pallas kernel is a
  custom call named after the jitted kernel entry point
  (``%xnor_mismatch_pallas.26 = s32[2048,8192]{...} custom-call(u32[...]
  ...)``), so its output and operand shapes are read off the name.

Busy time is the union of the program executions on a device, clipped to
the interval and averaged over the devices; the idle share is what is
left of the interval.  Per-execution and per-kernel readings keep only
what ran wholly inside it.  Each idle gap is labelled by the programs on
either side of it, which names what the host was doing between them.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re

WINDOW_SPAN = "perfbench_window"
_SHAPE = re.compile(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]")
_OP = re.compile(r"^%([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.[0-9]+)? = ")


def _shapes(text: str) -> list:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def parse_op(text: str):
    """(kernel, output shape, [(dtype, shape)] of operands) of one op
    event's HLO text; operands only for custom calls."""
    m = _OP.match(text)
    name = m.group(1) if m else text.split(" ")[0].lstrip("%")
    rest = text.partition(" = ")[2]
    out = _shapes(rest.split(" ", 1)[0])
    operands = []
    if "custom-call(" in rest:
        body = rest.split("custom-call(", 1)[1].split(
            ", custom_call_target=", 1)[0]
        operands = _shapes(body)
    return name, (out[0] if out else None), operands


def module_name(text: str) -> str:
    """``jit__decode(1392...)`` -> ``jit__decode``."""
    return text.split("(", 1)[0]


def events(pd) -> dict:
    """The plain-data view of a ``ProfileData``: the host window span and
    each TPU plane's module and op events (ns)."""
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            dev = {"name": plane.name, "modules": [], "ops": []}
            for key, line in (("modules", "XLA Modules"), ("ops", "XLA Ops")):
                if line in lines:
                    dev[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in lines[line].events]
            devices.append(dev)
    return {"window": window, "devices": devices}


def _union(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


class Reduced:
    """The traced interval of one run, on the TPU planes."""

    def __init__(self, ev: dict):
        devs = ev["devices"]
        self.t0, self.t1 = ev["window"]
        inside = lambda s, d: s >= self.t0 and s + d <= self.t1  # noqa: E731
        self.modules = []  # (device, name, start, dur) in the interval
        self.ops = []  # (kernel, out, operands, dur)
        self.op_time = collections.Counter()
        busy = []
        for i, dv in enumerate(devs):
            mods = [(module_name(n), s, d) for n, s, d in dv["modules"]
                    if inside(s, d)]
            self.modules += [(i,) + m for m in mods]
            # a program still running as the interval opens or closes is
            # busy for the part of it inside the interval
            busy.append(_union(
                (max(s, self.t0), min(s + d, self.t1))
                for _, s, d in dv["modules"] if s < self.t1 and s + d > self.t0))
            for n, s, d in dv["ops"]:
                if inside(s, d):
                    k, out, operands = parse_op(n)
                    self.op_time[k] += d
                    self.ops.append((k, out, operands, d))
        self.window_s = (self.t1 - self.t0) * 1e-9
        self.busy_s = (sum(busy) / max(len(busy), 1)) * 1e-9

    def executions(self, module: str) -> list:
        """Device durations (s) of each execution of a jitted program."""
        return [d * 1e-9 for _, n, _, d in self.modules if n == module]

    def kernel_calls(self, kernel: str) -> list:
        """(output, operands, seconds) of each call of a kernel."""
        return [(out, opr, d * 1e-9) for k, out, opr, d in self.ops
                if k == kernel]

    def breakdown(self, top: int = 10) -> dict:
        gaps = collections.Counter()
        per_dev = collections.defaultdict(list)
        for dev, n, s, d in self.modules:
            per_dev[dev].append((s, s + d, n))
        for mods in per_dev.values():
            mods.sort()
            for (s0, e0, n0), (s1, e1, n1) in zip(mods, mods[1:]):
                if s1 > e0:
                    gaps[f"{n0} -> {n1}"] += (s1 - e0) * 1e-9
        return {
            "device_ops": [[k, v * 1e-9] for k, v in
                           self.op_time.most_common(top)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(top)],
        }


def load(trace_dir: str) -> Reduced | None:
    """Reduce the newest trace under ``trace_dir``; None when it holds no
    TPU plane (a CPU run has no device to read) or no window span."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    ev = events(ProfileData.from_file(paths[-1]))
    if ev["window"] is None or not any(d["modules"] for d in ev["devices"]):
        return None
    return Reduced(ev)


def load_events(path: str) -> dict:
    with open(path) as f:
        ev = json.load(f)
    ev["window"] = tuple(ev["window"])
    for dv in ev["devices"]:
        dv["modules"] = [tuple(m) for m in dv["modules"]]
        dv["ops"] = [tuple(o) for o in dv["ops"]]
    return ev
