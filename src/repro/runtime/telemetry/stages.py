"""Which search stage each op of a compiled executable belongs to.

The search runs each stage of Algorithm 2 under a named scope
(`repro.core.search.STAGES`), and JAX writes the scope path into each op's
`op_name` metadata. The compiler keeps that metadata on most ops, but not
on all: a TPU fusion carries it only on the instructions inside its fused
computation, and the copies and relayout loops the compiler inserts carry
none. So
an op's stage is, in this order:

  1. the innermost stage scope in its own `op_name` (innermost, because the
     prefetch's exchange is issued from inside the step);
  2. for an op that runs a computation as part of itself (a fusion, a
     scatter's combiner), the stage most of that computation's ops have;
  3. the one stage of the ops that use its result, else the one stage of
     the ops it reads, within its computation (repeated until nothing
     changes): what the compiler inserts serves its neighbours;
  4. the stage of the op that calls its computation (a fusion, or a loop
     the compiler made, which got its stage from rule 3).

The search's own loop has no stage: its body's ops keep theirs.

An op none of these reaches has no stage (None).
"""
from __future__ import annotations

import collections
import re

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAMES = r"=\{?(%[\w.\-]+(?:,\s*%[\w.\-]+)*)"
_FUSED = re.compile(r"\b(?:calls|to_apply|called_computations)" + _NAMES)
_BODIES = re.compile(r"\b(?:body|condition|branch_computations)" + _NAMES)
_NAME = re.compile(r"%([\w.\-]+)")


class _Op:
    __slots__ = ("stage", "fused", "bodies", "operands", "users",
                 "computation")

    def __init__(self, stage, fused, bodies, operands, computation) -> None:
        self.stage = stage
        self.fused = fused              # computations it runs as itself
        self.bodies = bodies            # computations it runs as a loop
        self.operands = operands
        self.users: list[str] = []
        self.computation = computation


def _names(regex: re.Pattern, text: str) -> list[str]:
    return [n.strip().lstrip("%") for g in regex.findall(text)
            for n in g.split(",")]


def _own_stage(line: str, stages: tuple) -> str | None:
    meta = _OP_NAME.search(line)
    if meta is None:
        return None
    found = set()
    for path in meta.group(1).split(";"):   # merged ops join their names
        inner = [c for c in path.split("/") if c in stages]
        if inner:
            found.add(inner[-1])
    return found.pop() if len(found) == 1 else None


def _operand_list(rest: str) -> str:
    """The text inside `opcode(...)` of `<type> opcode(...), ...`."""
    if rest.startswith("("):                 # a tuple type
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:                                    # layouts hold parentheses
        rest = rest.partition(" ")[2]
    start = rest.find("(")
    if start < 0:
        return ""
    depth = 0
    for i in range(start, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            return rest[start + 1:i]
    return ""


def _parse(hlo_text: str, stages: tuple) -> tuple[dict, dict]:
    ops: dict[str, _Op] = {}
    members: dict[str, list[str]] = collections.defaultdict(list)
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(1)
        rest = line[m.end():]
        ops[name] = _Op(_own_stage(line, stages), _names(_FUSED, rest),
                        _names(_BODIES, rest),
                        _NAME.findall(_operand_list(rest)), computation)
        members[computation].append(name)
    for name, op in ops.items():
        for o in op.operands:
            if o in ops:
                ops[o].users.append(name)
    return ops, members


def _called_stage(op: _Op, ops: dict, members: dict, seen: set):
    counts: collections.Counter = collections.Counter()
    for comp in op.fused:
        if comp in seen:
            continue
        seen.add(comp)
        for n in members.get(comp, ()):
            inner = ops[n]
            st = inner.stage or _called_stage(inner, ops, members, seen)
            if st is not None:
                counts[st] += 1
    return counts.most_common(1)[0][0] if counts else None


def _unique(stages) -> str | None:
    found = {s for s in stages if s is not None}
    return found.pop() if len(found) == 1 else None


def _spread(ops: dict, via: str) -> bool:
    """Give each op with no stage the one stage of its `via` neighbours
    ("users" or "operands"), until nothing changes; whether any op got one."""
    spread, changed = False, True
    while changed:
        changed = False
        for op in ops.values():
            if op.stage is None:
                st = _unique(ops[n].stage for n in getattr(op, via)
                             if n in ops)
                if st is not None:
                    op.stage = st
                    changed = spread = True
    return spread


def op_stages(hlo_text: str, stages: tuple) -> dict[str, str | None]:
    """HLO instruction name -> its stage (one of `stages`) or None, from a
    compiled module's text (see the module docstring for the rules)."""
    ops, members = _parse(hlo_text, stages)
    for op in ops.values():
        if op.stage is None and op.fused:
            op.stage = _called_stage(op, ops, members, set())
    while _spread(ops, "users") or _spread(ops, "operands"):
        pass
    callers = {c: name for name, op in ops.items()
               for c in op.fused + op.bodies}
    out = {}
    for name, op in ops.items():
        st, comp = op.stage, op.computation
        while st is None and comp in callers:
            caller = ops[callers[comp]]
            st, comp = caller.stage, caller.computation
        out[name] = st
    return out
