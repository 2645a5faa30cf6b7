#!/usr/bin/env python3
"""What each operation of a compiled program reads and writes, from its
optimised HLO text: the table behind PERF.md section 5's "which leaf, how many
bytes, needed or not" (PR 43).

    python3 tools/hlo_table.py chiprun_out/hlo_decode.<config>.txt [trace_decode.<config>.json [shape]]

``tools/time_prefill.py --decode --trace <shapes> --hlo`` writes both files and
prints this table for every operation over 0.05 ms a step.  Nothing here
touches a device: the text is parsed, an operand is followed back through
loops, tuples, bitcasts and copies to the entry parameter it came from (its
``op_name`` is the parameter tree's path, ``params['moe_layers']['w_uq']``),
and a fusion that only slices a layer out of a stacked operand is charged the
slice, not the stack.  A Pallas call's operands are whole pools it DMAs pages
of: its bytes are the kernel's own count, not derivable here (``None``).
"""

import json
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
        "s64": 8, "u64": 8, "f64": 8}
ARRAY = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(ITEM))
# operations that hand their first operand on, re-laid out or not
PASS = ("bitcast", "copy", "copy-start", "copy-done", "slice-start", "slice-done", "opt-barrier", "get-tuple-element",
        "custom-call")


# operations that move nothing themselves
STRUCTURE = ("parameter", "tuple", "while", "get-tuple-element", "bitcast", "opt-barrier", "copy-done", "slice-done", "conditional")


# operations whose result is a part of their operand: they read what they write, once
SLICES = ("slice", "dynamic-slice", "slice-start", "copy-start")


class Op(NamedTuple):
    name: str
    shape: str
    opcode: str
    operands: Tuple[str, ...]
    attrs: str
    computation: str


def shape_bytes(shape: str) -> int:
    total = 0
    for dtype, dims in ARRAY.findall(shape):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n * ITEM[dtype]
    return total


def _balanced(text: str, start: int) -> int:
    """Index just past the bracket that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += text[i] in "([{"
        depth -= text[i] in ")]}"
        if depth == 0:
            return i + 1
    return len(text)


def parse(text: str) -> Dict[str, Dict[str, Op]]:
    """``{computation: {instruction: Op}}`` of an HLO module's text; the entry
    computation is under ``"ENTRY"`` too."""
    comps: Dict[str, Dict[str, Op]] = {}
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(2)
            comps[comp] = {}
            if head.group(1):
                comps["ENTRY"] = comps[comp]
            continue
        m = re.match(r"^\s+(?:ROOT )?%([\w.\-]+) = ", line)
        if not m or comp is None:
            continue
        rest = line[m.end():]
        end = _balanced(rest, 0) if rest[0] == "(" else next(
            i for i in range(len(rest) + 1) if i == len(rest) or (rest[i] == " " and rest[:i].count("{") == rest[:i].count("}")))
        shape, rest = rest[:end], rest[end:].lstrip()
        paren = rest.find("(")
        close = _balanced(rest, paren)
        operands = tuple(re.findall(r"%([\w.\-]+)", rest[paren:close]))
        opcode = rest[:paren]  # a parameter keeps its number, "(3)", at the head of its attributes
        comps[comp][m.group(1)] = Op(m.group(1), shape, opcode, operands, rest[paren if opcode == "parameter" else close:], comp)
    return comps


def _callers(comps) -> Dict[str, Op]:
    """The ``while`` that runs each loop body."""
    out = {}
    for ops in comps.values():
        for op in ops.values():
            body = re.search(r"body=%([\w.\-]+)", op.attrs) if op.opcode == "while" else None
            if body:
                out[body.group(1)] = op
    return out


def leaf(comps, op: Op, callers=None, via=(), depth=0) -> str:
    """The entry parameter ``op`` is made from, as the parameter tree's path,
    with the copies it passed through (``copy.259 of params[...]``); an
    operation that computes is named as itself."""
    callers = _callers(comps) if callers is None else callers
    ops = comps[op.computation]
    said = " of ".join(via + ("",)) if via else ""
    if depth > 40:
        return said + op.name
    if op.opcode == "parameter":
        if ops is comps["ENTRY"]:
            name = re.search(r'op_name="([^"]*)"', op.attrs)
            return said + (name.group(1).replace("\\'", "'") if name else op.name)
        return said + op.name  # a loop's whole tuple
    if op.opcode == "get-tuple-element":
        src = ops[op.operands[0]]
        index = int(re.search(r"index=(\d+)", op.attrs).group(1))
        if src.opcode == "parameter" and op.computation in callers:  # a loop's argument: its initial value
            loop = callers[op.computation]
            init = comps[loop.computation][loop.operands[0]]
            if init.opcode == "tuple":
                return leaf(comps, comps[loop.computation][init.operands[index]], callers, via, depth + 1)
        if src.opcode in ("tuple", "opt-barrier", "copy-start", "slice-start"):
            inner = src if src.opcode == "tuple" else ops[src.operands[0]]
            if inner.opcode == "tuple":
                return leaf(comps, ops[inner.operands[index]], callers, via, depth + 1)
            return leaf(comps, inner, callers, via, depth + 1)
        return said + op.name
    if op.opcode in PASS and op.operands and (op.opcode != "custom-call" or "ConcatBitcast" in op.attrs):
        moved = via + (op.name,) if op.opcode == "copy" else via
        return leaf(comps, ops[op.operands[0]], callers, moved, depth + 1)
    return said + op.name


def first_array_bytes(shape: str) -> int:
    m = ARRAY.search(shape)
    return shape_bytes(m.group(0)) if m else 0


def _sliced_bytes(comps, fusion: Op, index: int) -> Optional[int]:
    """What ``fusion`` reads of its operand ``index`` if all it does with it is
    ``dynamic-slice`` or ``gather`` (directly, behind a bitcast or in a fusion
    nested in it)."""
    called = re.search(r"calls=%([\w.\-]+)", fusion.attrs) if fusion.opcode == "fusion" else None
    inner = comps.get(called.group(1)) if called else None
    param = next((p for p in (inner or {}).values() if p.opcode == "parameter" and p.attrs.startswith(f"({index})")), None)
    if param is None:
        return None

    def users(name):  # through bitcasts
        for u in inner.values():
            if name in u.operands:
                yield from (users(u.name) if u.opcode == "bitcast" else [(u, u.operands.index(name))])

    total = 0
    for user, at in users(param.name):
        part = shape_bytes(user.shape) if user.opcode in ("dynamic-slice", "gather") and at == 0 else _sliced_bytes(comps, user, at)
        if part is None:
            return None
        total += part
    return total or None


def bytes_read(comps, op: Op) -> Optional[List[int]]:
    """Bytes of each operand as the operation reads it: a slice reads what it
    yields, and so does a fusion's parameter that feeds only ``dynamic-slice``
    (the layer scan's slice of a stack, fused into the dot that consumes it)."""
    if op.opcode == "custom-call" and "tpu_custom_call" in op.attrs:
        return None
    if op.opcode in SLICES:
        return [first_array_bytes(op.shape)]
    ops = comps[op.computation]
    sizes = [shape_bytes(ops[o].shape) if o in ops else 0 for o in op.operands]
    for i in range(len(sizes)):
        sliced = _sliced_bytes(comps, op, i)
        if sliced is not None:
            sizes[i] = sliced
    return sizes


def bytes_written(op: Op) -> Optional[int]:
    if op.opcode == "custom-call" and "tpu_custom_call" in op.attrs:
        return None  # its results alias the pools it patches a row of
    return first_array_bytes(op.shape) if op.opcode in SLICES else shape_bytes(op.shape)


def scope_of(op: Op) -> str:
    """The block's scope from ``op_name``: what stays of the path once the
    program, the loops and the primitive itself are taken off."""
    m = re.search(r'op_name="([^"]*)"', op.attrs)
    if not m:
        return ""
    parts = [p for p in m.group(1).split("/")[1:-1] if p not in ("while", "body", "cond", "closed_call")]
    return "/".join(parts[:2])


def table(text: str, op_ms: Dict[str, float], threshold_ms: float = 0.05) -> List[dict]:
    """A row for every operation of ``op_ms`` (name -> ms a step, from a trace
    of the same compile) at or over ``threshold_ms``."""
    comps = parse(text)
    callers = _callers(comps)
    by_name = {op.name: op for name, ops in comps.items() if not name.startswith("fused_computation") for op in ops.values()}
    rows = []
    for name, ms in sorted(op_ms.items(), key=lambda kv: -kv[1]):
        op = by_name.get(name)
        if ms < threshold_ms or op is None:
            continue
        read, written = bytes_read(comps, op), bytes_written(op)
        operands = [comps[op.computation][o] for o in op.operands if o in comps[op.computation]]
        big = sorted(zip(read or [0] * len(operands), operands), key=lambda x: -x[0])[:2]
        rows.append({
            "op": name, "ms": ms, "scope": scope_of(op), "opcode": op.opcode,
            "mb_read": None if read is None else round(sum(read) / 1e6, 2),
            "mb_written": None if written is None else round(written / 1e6, 2),
            "reads": [f"{leaf(comps, o, callers)} ({b / 1e6:.1f} MB)" if read is not None else leaf(comps, o, callers)
                      for b, o in big if read is None or b >= 1e6],
        })
    return rows


def render(rows: List[dict], what: str = "ms a step") -> str:
    lines = [f"op | {what} | scope | MB read | MB written | largest operands"]
    for r in rows:
        lines.append(f"{r['op']} | {r['ms']:.4f} | {r['scope'] or '-'} | {r['mb_read']} | {r['mb_written']} | {'; '.join(r['reads'])}")
    return "\n".join(lines)


def main(argv: List[str]) -> int:
    text = open(argv[1]).read()
    if len(argv) > 2:
        traced = json.load(open(argv[2]))
        print(render(table(text, traced[argv[3] if len(argv) > 3 else next(iter(traced))]["op_ms"])))
        return 0
    # no trace, so no time: every operation that reads or writes 4 MB or more, by the larger of the two
    comps = parse(text)
    moved = lambda op: max(sum(bytes_read(comps, op) or [0]), bytes_written(op) or 0)  # noqa: E731
    mb = {op.name: moved(op) / 1e6 for name, ops in comps.items() if not name.startswith("fused_computation")
          for op in ops.values() if op.opcode not in STRUCTURE and moved(op) >= 4e6}
    print(render(table(text, mb), what="MB moved"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
