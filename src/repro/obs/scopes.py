"""Stable names for the parts of a serving step, read back from HLO.

The paged steps (``serving/paging/attention.py``) and the transformer
layer (``models/transformer.py``) open ``jax.named_scope``s around each
part of the work:

* ``kv_gather`` — ``gather_blocks``: the block-table gather and its
  relayout into the dense per-layer view;
* ``attn`` — ``attn_norm`` through ``wo``, the residual add included;
* ``mlp`` — ``ffn_norm`` through ``w_down``, the residual add included;
  in an MoE layer (``models/moe.py`` ``moe_serve``) two scopes inside it
  take their parts:
* ``moe_route`` — the router's logits, softmax and top-k;
* ``moe_experts`` — the routed experts (the routed-expert kernel, or the
  grouped products of a prefill chunk) and their weighted combine;
* ``unembed`` — final norm, logits and argmax;
* ``kv_write`` — the arena scatter of the new K/V.

A scope is metadata only: it changes no computation, but XLA keeps it in
each instruction's ``metadata={op_name="jit(f)/.../mlp/dot_general"}``.
The profiler names a device op by its instruction, so ``op_scopes`` over
a compiled module's text (``jit(f).lower(...).compile().as_text()``)
says which part of the step each op of a trace belongs to, however the
compiler numbers its fusions.

Attribution, in order:

1. an instruction whose own ``op_name`` holds a scope takes the
   innermost one;
2. a fusion whose own ``op_name`` holds none takes the most common scope
   of the instructions in its fused computation;
3. what is left (layout copies the compiler inserts, which carry the
   parameter's name or no metadata at all) takes the most common scope
   of its operands, else of its users, repeated until nothing changes —
   a copy of the KV arena that feeds the gather is KV paging.
   Parameters and constants run no op and pass no scope on: a weight
   read by two parts of the step must not tie them together.

Instructions that reach no scope are left out of the map.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Tuple

SCOPES = ("kv_gather", "attn", "mlp", "moe_route", "moe_experts",
          "unembed", "kv_write")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")   # unindented
_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_INERT = ("parameter", "constant")


def scope_of(op_name: str) -> str:
    """The innermost scope named in an ``op_name`` path, or ''."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return ""


def _parse(hlo_text: str):
    """computation → [(name, opcode, op_name, operands, called)]."""
    comps: Dict[str, List[Tuple[str, str, str, List[str], str]]] = {}
    body = None
    for line in hlo_text.splitlines():
        if body is None:
            m = _HEADER.match(line)
            if m and not line.startswith("HloModule"):
                body = comps.setdefault(m.group(1), [])
            continue
        if line.strip() == "}":
            body = None
            continue
        m = _INST.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        opcode = op.group(1) if op else ""
        args = rest[op.end():] if op else ""
        md = _OP_NAME.search(rest)
        called = _CALLS.search(rest)
        body.append((name, opcode, md.group(1) if md else "",
                     _REF.findall(args.split("metadata=", 1)[0]),
                     called.group(1) if called else ""))
    return comps


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` for every instruction of a compiled
    module's text that can run as a device op and reaches a scope (see
    the module docstring for the order of attribution)."""
    comps = _parse(hlo_text)
    direct = {i[0]: scope_of(i[2]) for insts in comps.values()
              for i in insts}
    nested = {i[4] for insts in comps.values() for i in insts if i[4]}
    out: Dict[str, str] = {}
    for cname, insts in comps.items():
        if cname in nested:
            continue                   # fused bodies and reducers
        users: Dict[str, List[str]] = collections.defaultdict(list)
        for name, opcode, _, operands, called in insts:
            for o in operands:
                users[o].append(name)
            scope = direct[name]
            if not scope and opcode == "fusion" and called in comps:
                inner = [direct[i[0]] for i in comps[called]
                         if direct[i[0]]]
                if inner:
                    scope = collections.Counter(inner).most_common(1)[0][0]
            if scope:
                out[name] = scope
        operands_of = {i[0]: i[3] for i in insts}
        todo = [i[0] for i in insts
                if i[0] not in out and i[1] not in _INERT]
        changed = True
        while changed and todo:
            changed = False
            for name in list(todo):
                near = ([out[o] for o in operands_of[name] if o in out]
                        or [out[u] for u in users[name] if u in out])
                if near:
                    out[name] = collections.Counter(near).most_common(1)[0][0]
                    todo.remove(name)
                    changed = True
    return out
