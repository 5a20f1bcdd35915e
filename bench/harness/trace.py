"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the result's ``breakdown`` read.

Planes whose name starts with ``/device:TPU:`` are the chips. On each, the
``XLA Ops`` line holds one event per HLO instruction run, named by the
instruction's HLO text (``%sort.299 = (s32[...], f32[...]) sort(...)``,
``%fusion.418 = f32[...] fusion(...), kind=kCustom, calls=...``; a
``while`` loop's event spans its body's), and the ``XLA Modules`` line one
event per program run (``jit__local_chunk(<id>)``), under the same name as
the program's ``HloProto`` in the ``/host:metadata`` plane (``harness/hlo.py``).
The host plane holds the spans the benchmark opens with
``jax.profiler.TraceAnnotation`` and, from the Python tracer, the host's
function calls, on the same clock (read on a TPU v5 lite trace, jax 0.9.0).
"""

from __future__ import annotations

import bisect
import heapq
import dataclasses
import re

from harness import hlo

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns


def _events(line) -> list[Event]:
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def union_length(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to ``[lo, hi)``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if e <= at:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


#: Ops that only hold other ops (a while loop's events span its body's).
CONTAINERS = {"while", "conditional", "call"}
MODULE_ID = re.compile(r"\(\d+\)$")


def opcode_of(rest: str) -> str:
    """The opcode in ``<shape> <opcode>(<operands>)...``; the shape may be a
    tuple with nested parentheses."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    m = re.match(r"\s*([a-z][a-z0-9\-]*)\(", rest[i:])
    return m.group(1) if m else ""


def program_name(run_name: str) -> str:
    """``jit__local_chunk`` of a program run's name ``jit__local_chunk(<id>)``."""
    return MODULE_ID.sub("", run_name)


def instruction(event: Event) -> tuple[str, str]:
    """``(instruction name, opcode)`` of an op event, whose name is the
    instruction's HLO text (``%sort.3 = (s32[...], ...) sort(...)``)."""
    m = re.match(r"%([\w.\-]+)\s*=\s*(.*)$", event.name)
    if not m:
        return event.name, event.name.split(".")[0]
    return m.group(1), opcode_of(m.group(2))


@dataclasses.dataclass(frozen=True)
class Op:
    module: str  # program run name, e.g. jit__local_chunk(4333896658921338384)
    instr: str  # instruction name, e.g. fusion.418
    opcode: str
    start: int  # ns
    end: int  # ns


def device_ops(ops: list[Event], modules: list[Event]) -> list[Op]:
    """The leaf op events of one chip (containers dropped), each with the
    program it ran in: the ``XLA Modules`` event whose run holds its start."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for e in ops:
        instr, op = instruction(e)
        if op in CONTAINERS:
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        module = mods[i].name if i >= 0 and e.start < mods[i].end else ""
        out.append(Op(module, instr, op, e.start, e.end))
    return out


@dataclasses.dataclass
class Profile:
    chips: list  # per chip, [Op] sorted by start
    modules: list  # per chip, [Event] of its program runs
    host: list  # [Event] of every host line: annotations, Python calls
    programs: dict  # {program run name: {instruction: hlo.Instr}}

    @classmethod
    def load(cls, path: str) -> "Profile":
        from jax.profiler import ProfileData

        with open(path, "rb") as f:
            raw = f.read()
        data = ProfileData.from_serialized_xspace(raw)
        chips, modules, host = [], [], []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                lines = {line.name: _events(line)
                         for line in plane.lines}
                mods = lines.get(MODULES_LINE, [])
                chips.append(sorted(device_ops(lines.get(OPS_LINE, []), mods),
                                    key=lambda o: o.start))
                modules.append(mods)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend(_events(line))
        return cls(chips=chips, modules=modules, host=host,
                   programs=hlo.programs_in_xspace(raw))

    def instr(self, op: Op) -> hlo.Instr | None:
        """What ``op`` runs, from the ``HloProto`` of the program it ran in;
        None where the trace holds no such instruction."""
        return self.programs.get(op.module, {}).get(op.instr)

    def span(self, name: str) -> tuple[int, int]:
        """The first host span called ``name``."""
        hits = [e for e in self.host if e.name == name]
        if not hits:
            raise KeyError(f"no host span {name!r} in the trace")
        first = min(hits, key=lambda e: e.start)
        return first.start, first.end

    def busy_window(self, span: str) -> tuple[float, float]:
        """(device busy seconds averaged over the chips, window seconds)
        over the host span ``span``: busy is the union of the op
        intervals."""
        lo, hi = self.span(span)
        per = [union_length([(o.start, o.end) for o in ops], lo, hi)
               for ops in self.chips]
        busy = sum(per) / len(per) if per else 0
        return busy * 1e-9, (hi - lo) * 1e-9

    def op_seconds(self, span: str, select) -> float:
        """Device seconds, averaged over the chips, of the ops in ``span``
        for which ``select(op)`` holds."""
        lo, hi = self.span(span)
        sums = [sum(min(o.end, hi) - max(o.start, lo) for o in ops
                    if o.end > lo and o.start < hi and select(o))
                for ops in self.chips]
        return (sum(sums) / len(sums)) * 1e-9 if sums else 0.0

    def module_runs(self, span: str, module: str) -> list[float]:
        """Device seconds of each run of program ``module`` inside ``span``,
        on the first chip."""
        lo, hi = self.span(span)
        return [(m.end - m.start) * 1e-9 for m in
                (self.modules[0] if self.modules else [])
                if program_name(m.name) == module
                and m.start >= lo and m.end <= hi]

    def breakdown(self, span: str, top: int = 10, label=None) -> dict:
        """The device ops that took most time in ``span`` on the first chip
        (``program/instruction``, then ``label(op)`` where given), and its
        idle stretches summed by the innermost host span open at their
        midpoint."""
        lo, hi = self.span(span)
        ops = [o for o in (self.chips[0] if self.chips else [])
               if o.end > lo and o.start < hi]
        busy = {}
        for o in ops:
            key = (f"{program_name(o.module)}/{o.instr}"
                   + (label(o) if label else ""))
            busy[key] = busy.get(key, 0) + min(o.end, hi) - max(o.start, lo)
        host = sorted(self.host, key=lambda e: e.start)
        idle = {}
        # one sweep over the gaps' midpoints in order: the spans begun so
        # far, shortest first; a span that has ended at one midpoint has
        # ended at every later one
        begun, nxt = [], 0
        for s, e in gaps([(o.start, o.end) for o in ops], lo, hi):
            mid = (s + e) // 2
            while nxt < len(host) and host[nxt].start <= mid:
                h = host[nxt]
                heapq.heappush(begun, (h.end - h.start, nxt, h))
                nxt += 1
            while begun and begun[0][2].end <= mid:
                heapq.heappop(begun)
            name = begun[0][2].name if begun else "(no host span)"
            idle[name] = idle.get(name, 0) + (e - s)

        def ranked(d):
            return [[k, v * 1e-9] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": ranked(busy), "idle_gaps": ranked(idle)}
