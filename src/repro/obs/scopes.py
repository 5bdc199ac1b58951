"""Which named scope each operation of a compiled program belongs to.

The model code traces its work under ``jax.named_scope``s, which XLA keeps
as op metadata (``op_name="jit(f)/.../mpgemm/cw/convert_element_type"``):

  * ``mpgemm`` — every mpGEMM, whatever mode implements it
    (``core/mpgemm.py``), with the sub-scopes ``table`` (the table
    precompute, shared or not) and ``cw`` (the CW build from packed planes,
    ``kernels/ref.py:build_cw``);
  * ``attention`` — between the q/k/v and the o projections: RoPE, the
    cache write, scores, softmax, values (``models/layers.py``);
  * ``lm_head`` — the LM head (``models/transformer.py``), whose mpGEMM is
    ``lm_head/mpgemm``.

:func:`op_scopes` maps each instruction of a compiled program's HLO text
(``jax.jit(f).lower(...).compile().as_text()``) to one label:
``mpgemm``, ``mpgemm/table``, ``mpgemm/cw`` (each possibly under
``lm_head/``), ``attention``, ``lm_head`` or ``other``. A fusion's own
metadata is that of its root alone, so a fusion is labelled by everything
it fuses: mpGEMM work wins over attention, attention over the LM head's
other ops; within mpGEMM the contraction wins over ``cw``, ``cw`` over
``table``. A device trace keys its ops by the same instruction names, so
the labels attribute device time to scopes.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

__all__ = ["scope_label", "op_scopes", "module_name"]

MPGEMM, TABLE, CW, ATTENTION, LM_HEAD, OTHER = (
    "mpgemm", "table", "cw", "attention", "lm_head", "other")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_CALLS = re.compile(r"calls=\{?(%?[\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def scope_label(op_name: str) -> str:
    """The label of one instruction's ``op_name`` metadata."""
    parts = op_name.split("/")
    head = LM_HEAD in parts
    if MPGEMM in parts:
        inner = parts[parts.index(MPGEMM) + 1:]
        sub = next((s for s in (CW, TABLE) if s in inner), None)
        label = MPGEMM if sub is None else f"{MPGEMM}/{sub}"
        return f"{LM_HEAD}/{label}" if head else label
    if ATTENTION in parts:
        return ATTENTION
    return LM_HEAD if head else OTHER


def _rank(label: str) -> Tuple[int, int]:
    if label.endswith(MPGEMM):
        return 3, 2
    if MPGEMM in label:
        return 3, 1 if label.endswith(CW) else 0
    return {ATTENTION: (2, 0), LM_HEAD: (1, 0)}.get(label, (0, 0))


def _parse(text: str) -> Dict[str, List[Tuple[str, str, List[str]]]]:
    """computation -> [(instruction, op_name, called computations)]."""
    comps: Dict[str, list] = {}
    body = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and body is not None:
            op = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            body.append((m.group(1), op.group(1) if op else "",
                         [c.strip().lstrip("%") for c in
                          calls.group(1).split(",")] if calls else []))
            continue
        m = _COMPUTATION.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            body = None
    return comps


def op_scopes(text: str) -> Dict[str, str]:
    """Instruction name (without ``%``) -> label, for every instruction of
    every computation in the HLO text."""
    comps = _parse(text)
    memo: Dict[str, str] = {}

    def best(op: str, calls: List[str]) -> str:
        return max([scope_label(op)] + [fused(c) for c in calls], key=_rank)

    def fused(comp: str) -> str:
        """Best label over a called computation, nested calls included
        (computations call each other without cycles)."""
        if comp not in memo:
            memo[comp] = max([OTHER] + [best(op, calls) for _, op, calls
                                        in comps.get(comp, ())], key=_rank)
        return memo[comp]

    return {name: best(op, calls) for instrs in comps.values()
            for name, op, calls in instrs}


def module_name(text: str) -> str:
    """The HLO module's name (``jit__decode_chunk_impl``), as a device trace
    names the program's runs."""
    m = _MODULE.search(text)
    return m.group(1) if m else ""
