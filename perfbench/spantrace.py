"""The program's own spans and scopes in a profiler trace, read beside
``tracereduce``.

* Host spans: the serving scheduler opens ``sched.step`` for each loop
  iteration and ``sched.admit``, ``sched.window``, ``sched.decode``,
  ``sched.sync`` and ``sched.emit`` inside it, with their args as event
  stats (``repro.serve.engine``, module docstring).  ``sched.step``
  carries the scheduler's counters at entry (``step_num``, ``host_us``,
  ``wait_us``) and its clock (``t_us``).
* Model scopes: ``jax.named_scope`` names (``embed``, ``attn``,
  ``attn/kv_write``, ``attn/attend``, ``ffn``, ``head``) reach each TPU op
  through its name stack, which the op's event metadata holds as the stat
  ``tf_op`` (``jit(_decode)/attn/attend/jit(flash_decode_paged)/...``).
  ``ProfileData`` shows event stats only, not metadata stats, so
  ``op_scopes`` reads the metadata from the ``.xplane.pb`` file's protobuf
  wire format itself (the XSpace schema: planes 1; a plane's name 2,
  event_metadata 4, stat_metadata 5; an event metadata's name 2 and
  stats 5; a stat's metadata_id 1 and value 2-7).

``Spans`` holds both over the traced interval and reduces them to the
per-layer readings and two breakdowns: device-idle seconds put down to the
innermost ``sched.*`` span that covers most of each idle interval, and
device seconds by ``<program>/<scope>``.  A trace of a program that opens
no span and names no scope reads as nothing: ``(none)``, ``(unscoped)``,
and None from every reading.
"""

from __future__ import annotations

import bisect
import collections
import functools
import glob
import json
import os
import re
import statistics

import tracereduce

SPAN_PREFIX = "sched."
NO_SPAN = "(none)"
NO_SCOPE = "(unscoped)"
_INSTR = re.compile(r"^%(\S+) = ")
_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_.-]*")


def instr_name(text: str) -> str:
    """``%fusion.22 = s32[16]{0} fusion(...)`` -> ``fusion.22``."""
    m = _INSTR.match(text)
    return m.group(1) if m else text.split(" ")[0].lstrip("%")


def program_id(module: str) -> str:
    """``jit__decode(1392757)`` -> ``1392757``."""
    return module.partition("(")[2].rstrip(")")


def scope_of(tf_op: str) -> str:
    """The named scopes of an op's name stack: its parts that are plain
    names, without the program and the op itself, so without nested jits
    and transforms (``jit(f)``) and einsum specs (``bsd,vd->bsv``):
    ``jit(_decode)/attn/attend/jit(f)/pallas_call:`` -> ``attn/attend``."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    return "/".join(p for p in path.split("/")[1:-1] if _NAME.fullmatch(p))


def under(scope: str, top: str) -> bool:
    return scope == top or scope.startswith(top + "/")


# ---------------------------------------------------------------------------
# the xplane file
# ---------------------------------------------------------------------------


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for every other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane_scopes(plane, out: dict) -> None:
    """Add ``program id -> {instruction: scope}`` of one TPU plane."""
    stat_names, metas = {}, []
    for f, v in _fields(plane):
        if f == 5:  # stat_metadata entry -> XStatMetadata
            for ef, ev in _fields(v):
                if ef == 2:
                    sid = name = None
                    for sf, sv in _fields(ev):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            name = _text(sv)
                    stat_names[sid] = name
        elif f == 4:  # event_metadata entry -> XEventMetadata
            for ef, ev in _fields(v):
                if ef == 2:
                    metas.append(ev)
    for md in metas:
        name, stats = "", {}
        for f, v in _fields(md):
            if f == 2:
                name = _text(v)
            elif f == 5:
                sid, val = None, None
                for sf, sv in _fields(v):
                    if sf == 1:
                        sid = sv
                    elif sf in (3, 4):
                        val = sv
                    elif sf == 5:
                        val = _text(sv)
                    elif sf == 7:  # a reference to an interned string
                        val = stat_names.get(sv)
                stats[stat_names.get(sid)] = val
        if "tf_op" in stats and "program_id" in stats:
            pid = str(stats["program_id"])
            out.setdefault(pid, {})[instr_name(name)] = scope_of(
                str(stats["tf_op"]))


def op_scopes(path: str) -> dict:
    """``program id -> {HLO instruction name: scope}`` for every op of
    the TPU planes whose metadata carries a name stack."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name = next((_text(v) for pf, v in _fields(plane) if pf == 2), "")
        if name.startswith("/device:TPU:"):
            _plane_scopes(plane, out)
    return out


def host_spans(pd) -> list:
    """[(name, start ns, duration ns, args)] of every ``sched.*`` span on
    the host planes."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    args = {k: v for k, v in e.stats
                            if not k.startswith("_")}
                    out.append((e.name, e.start_ns, e.duration_ns, args))
    return out


def events(path: str) -> dict:
    """``tracereduce.events`` of the trace at ``path`` with the host spans
    (``spans``) and the op scopes (``scopes``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ev = tracereduce.events(pd)
    ev["spans"] = host_spans(pd)
    ev["scopes"] = op_scopes(path)
    return ev


def trim(ev: dict) -> dict:
    """``ev`` with only what the traced interval needs: spans that overlap
    it and the scopes of the ops that ran in it."""
    t0, t1 = ev["window"]
    used = collections.defaultdict(set)
    for dv in ev["devices"]:
        starts = sorted(dv["modules"], key=lambda m: m[1])
        for mod, (text, _, _) in _placed(starts, dv["ops"], t0, t1):
            if mod is not None:
                used[program_id(mod[0])].add(instr_name(text))
    scopes = {p: {k: v for k, v in m.items() if k in used[p]}
              for p, m in ev["scopes"].items() if p in used}
    spans = [s for s in ev["spans"] if s[1] < t1 and s[1] + s[2] > t0]
    return dict(ev, spans=spans, scopes=scopes)


def save_events(ev: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(ev, f, separators=(",", ":"))


def load_events(path: str) -> dict:
    """A saved ``events`` dict: also what ``tracereduce.Reduced`` takes."""
    ev = tracereduce.load_events(path)
    ev["spans"] = [tuple(s) for s in ev["spans"]]
    return ev


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def _placed(mods: list, ops: list, t0: float, t1: float):
    """(module event or None, op event) of each op wholly inside [t0, t1],
    with ``mods`` sorted by start: the program execution it ran in."""
    starts = [m[1] for m in mods]
    for op in ops:
        _, s, d = op
        if s < t0 or s + d > t1:
            continue
        j = bisect.bisect_right(starts, s) - 1
        inside = j >= 0 and s < mods[j][1] + mods[j][2]
        yield (mods[j] if inside else None), op


def _idle(busy: list, t0: float, t1: float) -> list:
    """The intervals of [t0, t1] that no busy interval covers."""
    out, cur = [], t0
    for s, e in sorted(busy):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


class Spans:
    """The traced interval of one run: host spans and scoped device ops."""

    def __init__(self, ev: dict):
        self.t0, self.t1 = ev["window"]
        t0, t1 = self.t0, self.t1
        self.spans = [s for s in ev["spans"] if s[1] < t1 and s[1] + s[2] > t0]
        self.scopes = ev["scopes"]
        self.devices = ev["devices"]
        self._mods = [sorted(dv["modules"], key=lambda m: m[1])
                      for dv in self.devices]

    def scope(self, module: str, op_text: str) -> str:
        return self.scopes.get(program_id(module), {}).get(
            instr_name(op_text)) or NO_SCOPE

    def _owner(self, a: float, b: float) -> str:
        """The innermost span covering more than half of [a, b]; such a
        span holds the midpoint, and of two nested ones the inner is the
        shorter."""
        m, half = (a + b) / 2, (b - a) / 2
        best = None
        for name, s, d, _ in self.spans:
            if s <= m <= s + d and min(b, s + d) - max(a, s) > half:
                if best is None or d < best[1]:
                    best = (name, d)
        return best[0] if best else NO_SPAN

    def idle_by_span(self) -> dict:
        """Device-idle seconds of the interval by the span the host was
        in, averaged over the devices as ``Reduced.busy_s`` is."""
        out = collections.Counter()
        for mods in self._mods:
            busy = [(max(s, self.t0), min(s + d, self.t1))
                    for _, s, d in mods if s < self.t1 and s + d > self.t0]
            for a, b in _idle(busy, self.t0, self.t1):
                out[self._owner(a, b)] += (b - a) * 1e-9 / len(self._mods)
        return dict(out)

    def device_scopes(self) -> dict:
        """Device seconds of the ops wholly inside the interval by
        ``<program>/<scope>``, summed over the devices as
        ``Reduced.op_time`` is."""
        out = collections.Counter()
        for mods, dv in zip(self._mods, self.devices):
            for mod, (text, _, d) in _placed(mods, dv["ops"], self.t0,
                                             self.t1):
                if mod is None:
                    out[f"{NO_SPAN}/{NO_SCOPE}"] += d * 1e-9
                else:
                    out[f"{tracereduce.module_name(mod[0])}/"
                        f"{self.scope(mod[0], text)}"] += d * 1e-9
        return dict(out)

    def scope_ms(self, program: str, top: str) -> float | None:
        """Mean device milliseconds, per execution of ``program`` wholly
        inside the interval, of its ops under scope ``top``; None when no
        op of such an execution carries that scope."""
        runs, total, found = 0, 0.0, False
        for mods, dv in zip(self._mods, self.devices):
            mine = [m for m in mods if tracereduce.module_name(m[0]) == program
                    and m[1] >= self.t0 and m[1] + m[2] <= self.t1]
            runs += len(mine)
            for mod, (text, _, d) in _placed(mine, dv["ops"], self.t0,
                                             self.t1):
                if mod is not None and under(self.scope(mod[0], text), top):
                    total += d
                    found = True
        return total * 1e-6 / runs if found and runs else None

    def steps(self) -> list:
        """The ``sched.step`` spans that start inside the interval, in
        order."""
        return sorted((s for s in self.spans if s[0] == "sched.step"
                       and self.t0 <= s[1] <= self.t1), key=lambda s: s[1])

    def host_ms_per_iteration(self) -> float | None:
        """The median, over the iterations between the first and the last
        step that start inside the interval, of the scheduler's own count
        of host seconds in each (the change of ``host_us`` from one step's
        entry to the next), in ms.  The median and not the mean: a
        dispatch that blocks on the device (a decode step dispatched behind
        a prefill window waits for the window) counts as host time, and a
        few such iterations would dominate a mean."""
        steps = [s[3] for s in self.steps() if "host_us" in s[3]]
        per = [(b["host_us"] - a["host_us"]) / (b["step_num"] - a["step_num"])
               for a, b in zip(steps, steps[1:])
               if b["step_num"] > a["step_num"]]
        return statistics.median(per) * 1e-3 if per else None

    def breakdown(self) -> dict:
        """``idle_by_span`` and ``device_scopes``, largest first."""
        return {key: sorted(([k, v] for k, v in fn().items()),
                            key=lambda kv: -kv[1])
                for key, fn in (("idle_by_span", self.idle_by_span),
                                ("device_scopes", self.device_scopes))}


# ---------------------------------------------------------------------------
# the trace of the run being read
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> Spans:
    return Spans(events(path))


def of(obs) -> Spans | None:
    """The spans of the run being read: ``obs.spans`` where the run gives
    them; else those of the trace that ``obs.trace`` was reduced from, the
    newest one ``run.py`` keeps under ``<checkout>/.perfbench_trace`` while
    the run's readers run, if its traced interval is the one ``obs.trace``
    holds; None otherwise."""
    if getattr(obs, "spans", None) is not None:
        return obs.spans
    if getattr(obs, "trace", None) is None:
        return None
    import bench

    paths = glob.glob(os.path.join(
        str(bench.CHECKOUT), ".perfbench_trace", "*", "plugins", "profile",
        "*", "*.xplane.pb"))
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    sp = _load(path, os.path.getmtime(path))
    if (sp.t0, sp.t1) != (obs.trace.t0, obs.trace.t1):
        return None
    return sp
