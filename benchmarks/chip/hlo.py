"""Reading the compiled step's optimized HLO text.

Two readings, both kept here so that no change to the program moves them:

* collective operand bytes per execution of the program: the arithmetic of
  ``repro.launch.analysis.parse_collectives`` (copied, not imported), with
  each op weighted by the trip count of the while loops around it, since a
  scanned layer stack runs its body's collectives once per layer (the TPU
  compiler writes no ``known_trip_count``, so the count is read from the
  loop's condition);
* the class of every instruction (``matmul``, ``collective`` or ``other``),
  so that the device trace's op events, which carry the instruction names,
  can be summed by what the op does rather than guessed from its name.
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, List, Tuple

# --- copied from repro.launch.analysis ------------------------------------
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(", re.IGNORECASE)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total
# ---------------------------------------------------------------------------


COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
MATMUL_OPS = ("convolution", "dot")
CONTROL_OPS = ("while", "conditional", "call")  # events span their bodies

_EVENT_RE = re.compile(r"^%?([\w.\-]+)\s*=")
_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"(?:^|[\s}])([a-z][a-z0-9\-]*)\(")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")   # fusions, async wrappers
_BODY_RE = re.compile(r"\bbody=%?([\w.\-]+)")     # while loops
_COND_RE = re.compile(r"\bcondition=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((-?\d+)\)")
_LT_RE = re.compile(r"compare\(%?([\w.\-]+),\s*%?([\w.\-]+)\),\s*direction=LT")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _computations(hlo_text: str) -> Iterator[Tuple[str, List[str]]]:
    name, body = None, []
    for line in hlo_text.splitlines():
        if name is None:
            m = _HEADER_RE.match(line)
            if m:
                name, body = m.group(1), []
        elif line.strip() == "}":
            yield name, body
            name = None
        else:
            body.append(line)


def _opcode(rest: str) -> str:
    m = _OPCODE_RE.search(rest)
    return m.group(1) if m else ""


def _base(opcode: str) -> str:
    for suffix in ("-start", "-done", "-update"):
        if opcode.endswith(suffix):
            return opcode[: -len(suffix)]
    return opcode


class Program:
    """The instructions of one compiled module, by computation."""

    def __init__(self, hlo_text: str):
        # computation -> [(instruction, opcode, computations it runs, line)];
        # a while loop's body is not part of what the loop op itself does
        self.comps: Dict[str, List[Tuple[str, str, List[str], str]]] = {}
        self.loops: Dict[str, Tuple[str, str]] = {}  # body -> (caller, line)
        for comp, lines in _computations(hlo_text):
            instrs = []
            for line in lines:
                m = _INSTR_RE.match(line)
                if not m:
                    continue
                rest = m.group(2)
                instrs.append((m.group(1), _opcode(rest),
                               _CALLS_RE.findall(rest), line))
                for body in _BODY_RE.findall(rest):
                    self.loops[body] = (comp, line)
            self.comps[comp] = instrs
        self._kinds: Dict[str, set] = {}

    def _ops_within(self, comp: str) -> set:
        """Base opcodes of a computation and of all it calls."""
        if comp not in self._kinds:
            self._kinds[comp] = set()
            kinds = set()
            for _, op, calls, _ in self.comps.get(comp, ()):
                kinds.add(_base(op))
                for c in calls:
                    kinds |= self._ops_within(c)
            self._kinds[comp] = kinds
        return self._kinds[comp]

    def op_classes(self) -> Dict[str, str]:
        """Instruction name -> ``collective``, ``matmul``, ``control`` (a
        loop or call, whose trace event spans the ops it runs) or
        ``other``."""
        out = {}
        for comp, instrs in self.comps.items():
            for name, op, calls, _ in instrs:
                if op in CONTROL_OPS:
                    out[name] = "control"
                    continue
                kinds = {_base(op)}
                for c in calls:
                    kinds |= self._ops_within(c)
                if kinds & set(COLLECTIVE_OPS):
                    out[name] = "collective"
                elif kinds & set(MATMUL_OPS):
                    out[name] = "matmul"
                else:
                    out[name] = "other"
        return out

    def _trips(self, line: str) -> int:
        """Trip count of the while loop on ``line``: its ``known_trip_count``
        where the compiler wrote one, else the bound N of the condition
        ``counter < N`` (JAX's scans count from 0 in steps of 1); 1 where
        neither can be read."""
        m = _TRIP_RE.search(line)
        if m:
            return int(m.group(1))
        cond = _COND_RE.search(line)
        instrs = self.comps.get(cond.group(1), ()) if cond else ()
        consts = {name: int(c.group(1)) for name, op, _, l in instrs
                  if op == "constant" and (c := _CONST_RE.search(l))}
        for _, _, _, l in instrs:
            lt = _LT_RE.search(l)
            if lt and lt.group(2) in consts:
                return consts[lt.group(2)]
        return 1

    def trip_counts(self) -> Dict[str, int]:
        """Computation -> times it runs per execution of the module (nested
        loops multiply).  Computations that no loop runs count once."""
        parent: Dict[str, Tuple[str, int]] = {}
        for body, (comp, line) in self.loops.items():
            parent[body] = (comp, self._trips(line))
        for comp, instrs in self.comps.items():
            for _, _, calls, _ in instrs:
                for c in calls:
                    parent.setdefault(c, (comp, 1))

        def count(comp, depth=0):
            if comp not in parent or depth > 64:
                return 1
            up, n = parent[comp]
            return n * count(up, depth + 1)

        return {comp: count(comp) for comp in self.comps}


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Result-shape bytes of every collective op per execution of the
    module, by kind, as ``parse_collectives`` sums them (the ``-done`` of an
    async pair carries the shape), each weighted by its loop trip count."""
    prog = Program(hlo_text)
    trips = prog.trip_counts()
    out: Dict[str, int] = {}
    for comp, instrs in prog.comps.items():
        for _, _, _, line in instrs:
            m = _OP_RE.search(line)
            if not m or m.group(3) == "-start":
                continue
            kind = m.group(2).lower()
            nbytes = _shape_bytes(m.group(1)) * trips[comp]
            out[kind] = out.get(kind, 0) + nbytes
    return out


def instruction_name(event_name: str) -> str:
    """The HLO instruction a TPU op event is of: the trace names each op
    event by its HLO line (``%fusion.12 = f32[...] fusion(...), ...``)."""
    m = _EVENT_RE.match(event_name)
    return m.group(1) if m else event_name
