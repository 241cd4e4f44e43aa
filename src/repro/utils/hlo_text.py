"""Parsing of compiled (optimized) HLO text, shared by the HLO lint
(:mod:`repro.lint.hlo`) and the scope map of a device trace
(:mod:`repro.obs.scopes`)."""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_COMP_HEADER_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
# comma-separated callee lists only ever appear inside braces
# (branch_computations={%a, %b}); a bare ref is a single name
CALLEE_RE = re.compile(
    r"\b(condition|body|to_apply|calls|true_computation|false_computation|"
    r"branch_computations)="
    r"(?:\{([^}]*)\}|(%?[\w.\-]+))")
OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    lines: List[str]
    # (increments_depth, callee) — depth rises only through while bodies
    edges: List[Tuple[bool, str]]


def callee_names(m: "re.Match[str]") -> List[str]:
    """The computation names of one :data:`CALLEE_RE` match."""
    names = m.group(2).split(",") if m.group(2) else [m.group(3)]
    return [n.strip().lstrip("%") for n in names]


def parse_computations(compiled_text: str) -> Dict[str, Computation]:
    """Split compiled HLO text into its computations with call edges."""
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in compiled_text.splitlines():
        if cur is None:
            m = _COMP_HEADER_RE.match(line)
            if m:
                cur = Computation(name=m.group(2),
                                  is_entry=bool(m.group(1)),
                                  lines=[], edges=[])
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        cur.lines.append(line)
        for m in CALLEE_RE.finditer(line):
            is_loop = (m.group(1) == "body" and "while(" in line)
            for name in callee_names(m):
                cur.edges.append((is_loop, name))
    return comps
