"""Device time per ``jax.named_scope`` of a compiled program.

A TPU trace names each device operation by its HLO instruction
(``%fusion.95``) and not by the scope the program wrapped it in.  The
compiled module's text carries each instruction's ``op_name`` metadata
(``jit(step)/while/body/repro.mla/dot_general``), so

* ``op_scopes(hlo_text, scopes)`` maps each instruction to the innermost
  of ``scopes`` its ``op_name`` names.  Instructions under none of them
  are left out (copies, the program's own glue), and so is every
  ``while``, ``conditional`` and ``call``: the trace times such a
  container and, again, each operation of its body;
* ``scope_seconds(trace, program, op_scopes)`` sums the device seconds of
  each scope's operations in a reduced trace (``trace_reduce.reduce``'s
  ``op_s``, keyed ``program/op``).

A fusion carries the metadata of the operation it was built around, so an
operation fused into another scope's fusion counts there.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bmetadata=\{[^}]*"
                    r"\bop_name=\"([^\"]*)\"")
_CONTAINER = re.compile(r"\s(?:while|conditional|call)\(")


def _bare(op: str) -> str:
    return op.lstrip("%")


def op_scopes(hlo_text: str, scopes: Sequence[str]) -> Dict[str, str]:
    """``{instruction: scope}`` for the instructions under one of
    ``scopes`` (the innermost, where scopes nest)."""
    pat = re.compile(r"(?:^|/)(" + "|".join(re.escape(s) for s in
                                            sorted(scopes, key=len,
                                                   reverse=True))
                     + r")(?=/|$)")
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        mt = _INSTR.match(line)
        if not mt or _CONTAINER.search(line):
            continue
        found = pat.findall(mt.group(2))
        if found:
            out[mt.group(1)] = found[-1]
    return out


def scope_seconds(trace: Optional[Dict], program: str,
                  scopes_of: Dict[str, str]) -> Dict[str, float]:
    """Device seconds per scope of ``program``'s operations in ``trace``."""
    out: Dict[str, float] = {}
    if not trace or not scopes_of:
        return out
    prefix = program + "/"
    for name, sec in trace["op_s"].items():
        if not name.startswith(prefix):
            continue
        scope = scopes_of.get(_bare(name[len(prefix):]))
        if scope is not None:
            out[scope] = out.get(scope, 0.0) + sec
    return out
