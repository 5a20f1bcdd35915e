"""What each instruction of a program that ran does, read from the program's
``HloProto``, which a TPU profile carries for every program it saw (the
``/host:metadata`` plane: one event metadata per program, named like the
program's ``XLA Modules`` events, ``jit__local_chunk(<id>)``, with an
``Hlo Proto`` stat). So the trace events, named after instructions
(``%fusion.418 = ... fusion(...), kind=kCustom``), are classed by the
operations they run, fused ones included, and by the JAX name stack they came
from (``jit(_local_chunk)/while/body/jit(merge_gain)/mul``), from the very
programs that ran.

A small reader of the protobuf wire format does the decoding; the field
numbers are those of ``xplane.proto`` and ``xla/service/hlo.proto``.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Instr:
    ops: set  # its opcode and, for a fusion, every opcode it fuses
    scope: str  # its JAX name stack, or its fused root's ("" if none)


# -------------------------------------------------------- protobuf wire format
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of each field of one message: an int for a
    varint, a memoryview of the bytes otherwise."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _ints(value) -> list[int]:
    """A repeated integer field's entry: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


# ------------------------------------------------------------------ programs
# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto: name 1, instructions 2, id 5, root_id 6;
# HloInstructionProto: name 1, opcode 2, metadata 7 (OpMetadata.op_name 2),
# id 35, called_computation_ids 38.
def _instruction(buf) -> dict:
    out = {"called": []}
    for number, value in fields(buf):
        if number == 1:
            out["name"] = _text(value)
        elif number == 2:
            out["opcode"] = _text(value)
        elif number == 7:
            out["scope"] = next((_text(v) for k, v in fields(value) if k == 2),
                                "")
        elif number == 35:
            out["id"] = value
        elif number == 38:
            out["called"] += _ints(value)
    return out


def parse_module(buf) -> dict:
    """``{instruction name: Instr}`` of one program, from its serialized
    ``HloModuleProto``."""
    comps = {}
    for number, value in fields(buf):
        if number != 3:
            continue
        comp = {"instrs": [], "root": None}
        for k, v in fields(value):
            if k == 2:
                comp["instrs"].append(_instruction(v))
            elif k == 5:
                comp["id"] = v
            elif k == 6:
                comp["root"] = v
        comps[comp.get("id", len(comps))] = comp

    memo: dict[int, set] = {}

    def ops_of(cid: int) -> set:
        if cid not in memo:
            memo[cid] = set()
            for ins in comps.get(cid, {"instrs": []})["instrs"]:
                memo[cid].add(ins.get("opcode", ""))
                for c in ins["called"]:
                    memo[cid] |= ops_of(c)
        return memo[cid]

    def root_scope(cid: int) -> str:
        comp = comps.get(cid)
        if not comp or not comp["instrs"]:
            return ""
        root = next((i for i in comp["instrs"] if i.get("id") == comp["root"]),
                    comp["instrs"][-1])
        return root.get("scope", "")

    table = {}
    for comp in comps.values():
        for ins in comp["instrs"]:
            ops, scope = {ins.get("opcode", "")}, ins.get("scope", "")
            if ins.get("opcode") == "fusion":
                for c in ins["called"]:
                    ops |= ops_of(c)
                scope = scope or next(
                    (s for s in map(root_scope, ins["called"]) if s), "")
            table[ins["name"]] = Instr(ops, scope)
    return table


def parse_hlo_proto(buf) -> dict:
    """:func:`parse_module` of the module inside a serialized ``HloProto``."""
    module = next((v for k, v in fields(buf) if k == 1), b"")
    return parse_module(module)


# XSpace.planes = 1; XPlane: name 2, event_metadata 4, stat_metadata 5
# (map entries: key 1, value 2); XEventMetadata: name 2, stats 5;
# XStatMetadata: id 1, name 2; XStat: metadata_id 1, bytes_value 6.
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def programs_in_xspace(buf) -> dict:
    """``{program run name: {instruction: Instr}}`` of every program whose
    ``HloProto`` a serialized ``XSpace`` (an ``.xplane.pb`` file) holds."""
    out = {}
    for number, plane in fields(buf):
        if number != 1:
            continue
        name, events, stat_names = None, [], {}
        for k, v in fields(plane):
            if k == 2:
                name = _text(v)
            elif k == 4:
                events.append(next((x for j, x in fields(v) if j == 2), b""))
            elif k == 5:
                entry = dict(fields(next(x for j, x in fields(v) if j == 2)))
                stat_names[entry.get(1, 0)] = _text(entry.get(2, b""))
        if name != METADATA_PLANE:
            continue
        for ev in events:
            ev_name, proto = "", None
            for k, v in fields(ev):
                if k == 2:
                    ev_name = _text(v)
                elif k == 5:
                    stat = dict(fields(v))
                    if stat_names.get(stat.get(1)) == HLO_STAT and 6 in stat:
                        proto = stat[6]
            if proto is not None:
                out[ev_name] = parse_hlo_proto(proto)
    return out
