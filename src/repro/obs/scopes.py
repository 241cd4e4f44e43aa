"""Named scopes of a compiled program, read from its optimized HLO text.

``jax.named_scope`` writes each op's scope path into the op's
``metadata={op_name="jit(f)/local_sgd/while/body/conv/dot_general"}``.
Transforms wrap a scope's name (``transpose(jvp(conv))`` for the backward
pass of ``conv``), so a path component matches a scope when it is the name
itself or the name inside such wrappers. A device trace names each op by
its module and its HLO name; :func:`op_scopes` maps that name to the phase
and the layer the op ran in.

The compiler drops the metadata of some ops it builds: a fusion it makes
from a concatenate (its inputs written into one buffer) or a copy it adds
for a layout. Only an op with no ``op_name`` takes its scopes from
elsewhere: from the ops fused into it, then from the loop or branch op
whose body holds it. An op whose own ``op_name`` names no layer
(a fusion rooted at a bias add) keeps none, whatever is fused into it.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from repro.utils.hlo_text import (CALLEE_RE, OP_NAME_RE, callee_names,
                                  parse_computations)

Scopes = Tuple[Optional[str], Optional[str]]

_MODULE_RE = re.compile(r"^\s*HloModule\s+([^\s,]+)", re.M)
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_WRAPPED_RE = re.compile(r"^[\w\-]+\((.*)\)$")


def hlo_module_name(hlo_text: str) -> str:
    """The name in the module's ``HloModule <name>, ...`` header."""
    m = _MODULE_RE.search(hlo_text)
    if m is None:
        raise ValueError("no HloModule header in the HLO text")
    return m.group(1)


def scope_of(component: str) -> str:
    """A path component with its transform wrappers taken off:
    ``transpose(jvp(conv))`` -> ``conv``."""
    m = _WRAPPED_RE.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED_RE.match(component)
    return component


def path_scopes(op_name: str, phases: Sequence[str],
                layers: Sequence[str]) -> Scopes:
    """(phase, layer) of one ``op_name`` path: the outermost component
    that is a phase and the innermost that is a layer, each None where no
    component matches."""
    names = [scope_of(c) for c in op_name.split("/")]
    phase = next((n for n in names if n in phases), None)
    layer = next((n for n in reversed(names) if n in layers), None)
    return phase, layer


def _fill(scopes: Scopes, fallback: Scopes) -> Scopes:
    return scopes[0] or fallback[0], scopes[1] or fallback[1]


def op_scopes(hlo_text: str, phases: Sequence[str],
              layers: Sequence[str] = ()) -> Dict[str, Scopes]:
    """``{op: (phase or None, layer or None)}`` for every instruction of
    an optimized HLO module. An instruction's own ``op_name`` decides.
    One with no ``op_name`` takes the first phase and layer its fused ops
    name, then, for what is still missing, those of the loop or branch op
    that runs its computation."""
    comps = parse_computations(hlo_text)
    # per computation: (op, own scopes or None without an op_name,
    # fused callees, control callees)
    instrs: Dict[str, List[Tuple[str, Optional[Scopes], List[str],
                                 List[str]]]] = {}
    for comp in comps.values():
        rows = instrs.setdefault(comp.name, [])
        for line in comp.lines:
            m = _INSTR_RE.match(line)
            if m is None:
                continue
            name = OP_NAME_RE.search(line)
            own = (path_scopes(name.group(1), phases, layers) if name
                   else None)
            fused, control = [], []
            for c in CALLEE_RE.finditer(line):
                is_fused = c.group(1) == "calls" and " fusion(" in line
                (fused if is_fused else control).extend(callee_names(c))
            rows.append((m.group(1), own, fused, control))

    def first_named(comp: str) -> Scopes:
        named = [s for _, s, _, _ in instrs.get(comp, ()) if s]
        return (next((s[0] for s in named if s[0]), None),
                next((s[1] for s in named if s[1]), None))

    out: Dict[str, Scopes] = {}
    context = {c.name: (None, None) for c in comps.values() if c.is_entry}
    todo = list(context)
    while todo:
        comp = todo.pop()
        for op, own, fused, control in instrs.get(comp, ()):
            scopes = own
            if scopes is None:
                scopes = (None, None)
                for f in fused:
                    scopes = _fill(scopes, first_named(f))
                scopes = _fill(scopes, context[comp])
            out[op] = scopes
            for c in control:
                if c not in context:
                    context[c] = scopes
                    todo.append(c)
    # instructions of fused computations keep their own scopes
    for comp, rows in instrs.items():
        for op, own, _, _ in rows:
            out.setdefault(op, own or (None, None))
    return out
