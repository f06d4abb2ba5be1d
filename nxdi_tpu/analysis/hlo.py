"""Text-level views of a lowered/compiled program.

The auditor never interprets HLO semantically — it counts and maps things
that XLA spells out in the program text:

- **StableHLO** (``lowered.as_text()``): the ``@main`` signature carries a
  ``tf.aliasing_output`` / ``jax.buffer_donor`` attribute on every argument
  whose donation RESOLVED to an output alias. A donated-but-unaliased cache
  input is exactly the "two copies of the KV cache in HBM" failure mode.
- **optimized HLO** (``compiled.as_text()``): collectives exist only after
  the SPMD partitioner ran, so ``all-gather``/``all-reduce``/... are counted
  here. The layer stack is a ``lax.scan`` (a ``while`` loop in HLO), so the
  textual count is per *program*, not per layer — a policy regression that
  adds one collective to the loop body shows up as +1, regardless of depth.
  Memory layouts exist only here too: :func:`param_moves` finds the
  instructions that move a weight into another layout.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

#: collective op mnemonics as they appear in optimized HLO text.
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# an op DEFINITION: the opcode token directly before its operand paren —
# `... f32[...] all-reduce(...)` and the async halves `... (f32[...],
# f32[...]) all-reduce-start(...)` (tuple result types contain spaces, so the
# opcode may follow a `)` + space, not a single type token). `-done` ops take
# the start's tuple without a fresh operand list and are NOT counted again;
# operand references (`%all-reduce.5`) are excluded by the preceding-char
# class (never `%`/`.`).
_COLLECTIVE_DEF_RE = re.compile(
    r"(?:^|[\s)])("
    + "|".join(op.replace("-", "[-]") for op in COLLECTIVE_OPS)
    + r")(?:-start)?\(",
    re.M,
)


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Per-type counts of collective op *definitions* in optimized HLO."""
    counts = {op: 0 for op in COLLECTIVE_OPS}
    for m in _COLLECTIVE_DEF_RE.finditer(hlo_text):
        counts[m.group(1)] += 1
    return counts


def _main_signature(stablehlo_text: str) -> Optional[str]:
    """The argument list of ``func.func public @main(...)`` with nesting and
    quoted strings (sharding attrs contain braces) handled."""
    anchor = stablehlo_text.find("@main(")
    if anchor < 0:
        return None
    i = anchor + len("@main(")
    depth = 1
    in_quote = False
    out = []
    while i < len(stablehlo_text) and depth > 0:
        c = stablehlo_text[i]
        if in_quote:
            if c == '"' and stablehlo_text[i - 1] != "\\":
                in_quote = False
        elif c == '"':
            in_quote = True
        elif c in "(<[{":
            depth += 1
        elif c in ")>]}":
            depth -= 1
        if depth > 0:
            out.append(c)
        i += 1
    return "".join(out)


_ARG_RE = re.compile(r"%arg(\d+):")


def main_arg_segments(stablehlo_text: str) -> List[Tuple[int, str]]:
    """``[(arg_index, segment_text), ...]`` — one segment per ``@main`` arg,
    covering its type and attribute dictionary."""
    sig = _main_signature(stablehlo_text)
    if sig is None:
        return []
    marks = list(_ARG_RE.finditer(sig))
    segments = []
    for j, m in enumerate(marks):
        end = marks[j + 1].start() if j + 1 < len(marks) else len(sig)
        segments.append((int(m.group(1)), sig[m.start():end]))
    return segments


def aliased_arg_positions(stablehlo_text: str) -> Set[int]:
    """Positions (``%argN`` numbers) whose argument carries a resolved
    input/output alias or donor mark."""
    out = set()
    for idx, seg in main_arg_segments(stablehlo_text):
        if "tf.aliasing_output" in seg or "jax.buffer_donor" in seg:
            out.add(idx)
    return out


# -- weights moved into another layout ----------------------------------------

_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(")
_INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
_ARRAY_RE = re.compile(r"^(\w+)\[([\d,]*)\](?:\{([\d,]*))?")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_KIND_RE = re.compile(r"kind=(k\w+)")
# a value passes through these unchanged (a bitcast, an asynchronous move to
# another memory space and back)
_PASS_THROUGH = ("bitcast", "copy-start", "copy-done")


@dataclass(frozen=True)
class _Instr:
    name: str
    shape: str
    opcode: str
    operands: Tuple[str, ...]
    calls: Optional[str]
    number: Optional[int]  # a parameter's number
    kind: Optional[str] = None  # a fusion's kind: kLoop, kInput, kOutput, kCustom


def _closing(text: str, i: int) -> int:
    """Index just past the bracket group opening at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in "([{":
            depth += 1
        elif text[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _parse_instruction(line: str) -> Optional[_Instr]:
    m = _INSTRUCTION_RE.match(line)
    if not m:
        return None
    i = m.end()
    if line.startswith("(", i):  # a tuple result
        end = _closing(line, i)
    else:
        end = line.find(" ", i)
        if end < 0:
            return None
    shape = line[i:end]
    rest = line[end:].lstrip()
    op = re.match(r"([a-z][\w\-]*)\(", rest)
    if not op:
        return None
    args = rest[op.end() - 1:_closing(rest, op.end() - 1)]
    calls = _CALLS_RE.search(rest)
    kind = _KIND_RE.search(rest)
    number = int(args[1:-1]) if op.group(1) == "parameter" and args[1:-1].isdigit() else None
    return _Instr(m.group(1), shape, op.group(1), tuple(re.findall(r"%([\w.\-]+)", args)),
                  calls.group(1) if calls else None, number, kind.group(1) if kind else None)


def _array(shape: str):
    """``(dims, minor_to_major or None)`` of an array shape with its layout."""
    m = _ARRAY_RE.match(shape)
    if not m:
        return None, None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    m2m = tuple(int(d) for d in m.group(3).split(",") if d) if m.group(3) is not None else None
    return dims, m2m


def _without_dim0(m2m, rank):
    """A minor-to-major order with dimension 0 taken out (the scan's layer
    axis), renumbered; ``rank`` says whether the array still has it."""
    if m2m is None:
        return None
    if len(m2m) == rank:
        return tuple(d - 1 for d in m2m if d != 0)
    return m2m


@dataclass(frozen=True)
class ParamMove:
    """One instruction of an optimised program that moves a weight into
    another memory layout: a ``copy`` of a parameter leaf (at the program's
    entry) or of the layer scan's slice of one, or a slice of a stacked leaf
    taken straight into another layout. ``leaves`` are the candidate leaves'
    flat indices (several where leaves share a shape)."""

    instruction: str
    shape: str
    leaves: Tuple[int, ...]
    what: str  # "copy" | "copy of a layer's slice" | "slice into another layout"

    def __str__(self) -> str:
        return f"{self.what} {self.instruction} {self.shape} (leaves {list(self.leaves)})"


def param_moves(
    hlo_text: str,
    param_dims: Sequence[Tuple[int, ...]],
    kept_args: Optional[Sequence[int]] = None,
) -> List[ParamMove]:
    """The instructions of ``hlo_text`` (optimised HLO) that move a weight into
    another layout. ``param_dims``: the shape of each parameter leaf, in flat
    order (they lead the program's flat arguments); ``kept_args``: the flat
    argument index of each entry parameter (the lowering prunes unused ones;
    ``None`` = none pruned). Instructions inside a fusion are the fusion's own
    business (a dot may re-tile its operand in fast memory); a fusion whose
    root is a copy of its operand counts as a copy."""
    comps: Dict[str, List[_Instr]] = {}
    entry = None
    current: Optional[List[_Instr]] = None
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION_RE.match(line)
            if m:
                current = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if current is not None:
            ins = _parse_instruction(line)
            if ins is not None:
                current.append(ins)
    by_name = {i.name: i for body in comps.values() for i in body}
    fused = {i.calls for body in comps.values() for i in body if i.opcode == "fusion" and i.calls}
    roots = {name: body[-1] for name, body in comps.items() if body}

    stacked: Dict[Tuple[int, ...], List[int]] = {}
    for idx, dims in enumerate(param_dims):
        stacked.setdefault(tuple(dims), []).append(idx)
    n_params = len(param_dims)

    def source(ins: _Instr) -> _Instr:
        while ins.opcode in _PASS_THROUGH or (
            ins.opcode == "get-tuple-element" and ins.operands
            and by_name.get(ins.operands[0]) is not None
            and by_name[ins.operands[0]].opcode == "copy-start"
        ):
            nxt = by_name.get(ins.operands[0]) if ins.operands else None
            if nxt is None:
                break
            ins = nxt
        return ins

    def entry_leaf(ins: _Instr) -> Optional[int]:
        if ins.opcode != "parameter" or ins.number is None or ins not in comps.get(entry, ()):
            return None
        flat = kept_args[ins.number] if kept_args is not None else ins.number
        return flat if flat < n_params else None

    def sliced_leaves(ins: _Instr):
        """``(leaves, operand)`` where ``ins`` takes one layer of a stacked
        leaf: a dynamic-slice, or a fusion rooted in one."""
        root = ins
        if ins.opcode == "fusion":
            root = roots.get(ins.calls)
            while root is not None and root.opcode == "bitcast" and root.operands:
                root = by_name.get(root.operands[0])
        if root is None or root.opcode != "dynamic-slice" or not ins.operands:
            return (), None
        operand = by_name.get(ins.operands[0])
        dims, _ = _array(operand.shape) if operand is not None else (None, None)
        out, _ = _array(ins.shape)
        if not dims or out is None or len(dims) < 2:
            return (), None
        layer = int(_prod(dims[1:]))
        if int(_prod(out)) != layer:
            return (), None
        return tuple(stacked.get(dims, ())), operand

    def relaid_operands(ins: _Instr) -> List[str]:
        """What ``ins`` copies: its operand if it is a copy; the operands a
        loop fusion copies or transposes as they come in (a dot's fusion
        re-tiling its operand in fast memory is the dot's own business)."""
        if ins.opcode == "copy":
            return list(ins.operands[:1])
        if ins.opcode != "fusion" or ins.kind not in ("kLoop", "kInput"):
            return []
        out = []
        for inner in comps.get(ins.calls, ()):
            if inner.opcode in ("copy", "transpose") and inner.operands:
                arg = by_name.get(inner.operands[0])
                if arg is not None and arg.opcode == "parameter" and arg.number is not None \
                        and arg.number < len(ins.operands):
                    out.append(ins.operands[arg.number])
        return out

    moves: List[ParamMove] = []
    for comp, body in comps.items():
        if comp in fused:
            continue
        for ins in body:
            relaid = relaid_operands(ins)
            for operand in relaid:
                src = source(by_name.get(operand, ins))
                leaf = entry_leaf(src)
                if leaf is not None:
                    moves.append(ParamMove(ins.name, ins.shape, (leaf,), "copy"))
                    continue
                leaves, _ = sliced_leaves(src)
                if leaves:
                    moves.append(ParamMove(ins.name, ins.shape, leaves, "copy of a layer's slice"))
            if relaid:
                continue
            leaves, operand = sliced_leaves(ins)
            if leaves:
                dims, m2m = _array(operand.shape)
                out, out_m2m = _array(ins.shape)
                a = _without_dim0(m2m, len(dims))
                b = _without_dim0(out_m2m, len(dims)) if len(out) == len(dims) else out_m2m
                if a is not None and b is not None and a != b:
                    moves.append(ParamMove(ins.name, ins.shape, leaves, "slice into another layout"))
    return moves


def _prod(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n
