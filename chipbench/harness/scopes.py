"""Which layer of the program each traced device op belongs to, by the
named scopes the program puts around its work.

The solver core wraps its work in ``jax.named_scope``s: ``ode_field``
(every vector-field evaluation of the RK solvers), ``ode_ckpt_write``
(the trajectory-checkpoint and replay-buffer writes) and
``ode_aca_backward`` (the whole ACA backward sweep).  A scope becomes one
component of the ``metadata={op_name="..."}`` of every HLO instruction
built inside it, wrapped in the transformations that made the op:
``jit(step)/transpose(jvp(ode_aca_backward))/while/body/transpose(jvp(ode_field))/dot_general``.
A fusion is attributed by its root alone: by the op_name the fusion
carries, or where it carries none (XLA's rewrites drop some, such as a
scatter's), by the op_name nearest the root inside its fused
computation: a checkpoint scatter is named by the select that feeds it.

The trace names an op only by its HLO instruction (``Summary.op_s``,
``name[kernel]`` for a Pallas call), so ``op_names`` reads each
instruction's op_name out of the compiled programs' HLO text and
``scope_seconds`` sums the device self time of the ops under a scope.
Scopes nest and overlap: a field evaluation replayed by the backward
sweep is under both ``ode_field`` and ``ode_aca_backward``, and counts
in both.

The window's programs are the ``jax.stages.Compiled`` objects alive
while the per-layer readers run (``runner.run_cell`` holds
``Driver.programs()`` until its readers are done); the map is made once
per run and kept in ``ctx["scopes"]``.
"""

from __future__ import annotations

import gc
import re
import sys
from typing import Dict, Iterable, List, Optional

from .layers import RK_STAGE_MODULE, busy_total_s

FIELD = "ode_field"
CKPT_WRITE = "ode_ckpt_write"
ACA_BACKWARD = "ode_aca_backward"
SCOPES = (FIELD, CKPT_WRITE, ACA_BACKWARD)

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = ')
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$')
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_FUSION_CALLS = re.compile(r' fusion\(.*, calls=%([\w.\-]+)')


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name metadata ("" where it has none) of
    every instruction in a compiled program's HLO text; a fusion without
    one takes the op_name nearest its fused computation's root."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}          # fusion -> its fused computation
    body: Dict[str, List[str]] = {}     # computation -> instructions
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
                body[computation] = []
            elif line.strip() == "}":
                computation = None
            continue
        name = _OP_NAME.search(line)
        own[m.group(1)] = name.group(1) if name else ""
        f = _FUSION_CALLS.search(line)
        if f:
            calls[m.group(1)] = f.group(1)
        if computation is not None:
            body[computation].append(m.group(1))

    resolved: Dict[str, str] = {}

    def named(instr: str) -> str:
        if own[instr] or instr not in calls:
            return own[instr]
        if instr not in resolved:
            resolved[instr] = ""
            # the root is printed last: walk back from it
            for inner in reversed(body.get(calls[instr], [])):
                found = named(inner)
                if found:
                    resolved[instr] = found
                    break
        return resolved[instr]

    return {instr: named(instr) for instr in own}


def merge(maps: Iterable[Dict[str, str]]) -> Dict[str, Optional[str]]:
    """One map over several programs; a name two programs give different
    op_names is unknown (None)."""
    out: Dict[str, Optional[str]] = {}
    for m in maps:
        for name, op in m.items():
            out[name] = op if out.get(name, op) == op else None
    return out


def in_scope(op_name: Optional[str], scope: str) -> bool:
    """Whether ``scope`` is a component of ``op_name``'s path, bare or
    inside a transformation (``vmap(...)``, ``transpose(jvp(...))``)."""
    return bool(op_name) and re.search(
        r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)", op_name) \
        is not None


def _instr(op: str) -> str:
    return op.split("[", 1)[0]


def scope_seconds(op_s: Dict[str, float],
                  names: Dict[str, Optional[str]], scope: str) -> float:
    """Device self seconds of the traced ops under ``scope``."""
    return sum(secs for op, secs in op_s.items()
               if in_scope(names.get(_instr(op)), scope))


def unattributed(op_s: Dict[str, float],
                 names: Dict[str, Optional[str]]) -> Dict[str, float]:
    """Traced ops with no op_name, or not found in the programs."""
    return {op: secs for op, secs in op_s.items()
            if not names.get(_instr(op))}


def covered_seconds(op_s: Dict[str, float],
                    names: Dict[str, Optional[str]],
                    calls: Dict[str, Optional[str]]) -> float:
    """Device self seconds of the ops under any solver scope or in an
    rk_stage kernel, each op counted once."""
    return sum(secs for op, secs in op_s.items()
               if any(in_scope(names.get(_instr(op)), s) for s in SCOPES)
               or ("[" in op and calls.get(_instr(op)) == RK_STAGE_MODULE))


def live_programs() -> List:
    import jax

    return [o for o in gc.get_objects() if isinstance(o, jax.stages.Compiled)]


def layers_of(op: str, names: Dict[str, Optional[str]],
              calls: Dict[str, Optional[str]]) -> str:
    """The solver scopes an op is under, joined by "+"; its rk_stage
    kernel; or why it has none."""
    name = names.get(_instr(op))
    found = [s for s in SCOPES if in_scope(name, s)]
    if "[" in op and calls.get(_instr(op)) == RK_STAGE_MODULE:
        found.append(RK_STAGE_MODULE)
    if found:
        return "+".join(found)
    if name is None:
        return "not found" if _instr(op) not in names else "ambiguous"
    return "no op_name" if not name else "other: " + name[-60:]


def window_op_names(ctx, log=None) -> Dict[str, Optional[str]]:
    """The window programs' op_name map, made on first use and kept in
    ``ctx["scopes"]``; logs on standard error the share of busy time
    under the solver scopes and rk_stage kernels, the share whose op has
    no op_name or is not found, and the largest ops with their layers."""
    if "scopes" not in ctx:
        names = merge(op_names(p.as_text()) for p in live_programs())
        ctx["scopes"] = names
        s = ctx["summary"]
        calls = ctx.get("kernels", {})
        busy = busy_total_s(ctx)
        if busy > 0:
            lost = unattributed(s.op_s, names)
            covered = covered_seconds(s.op_s, names, calls)
            (log or _stderr)(
                f"scopes: {100 * covered / busy:.2f} % of busy time under "
                f"{'/'.join(SCOPES)} or in rk_stage kernels; "
                f"{100 * sum(lost.values()) / busy:.2f} % has no op_name "
                f"or is not found; largest ops: " + "; ".join(
                    f"{op} {secs:.4f} s ({layers_of(op, names, calls)})"
                    for op, secs in s.top_ops(12)))
    return ctx["scopes"]


def _stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def share(ctx, scope: str) -> Optional[float]:
    """Device self time under ``scope`` over the busy time (%), the
    denominator of ``rk_stage_share.train``; None where no program of
    the window carries the scope (a program without it) or nothing ran.
    """
    names = window_op_names(ctx)
    busy = busy_total_s(ctx)
    if busy <= 0 or not any(in_scope(n, scope) for n in names.values()):
        return None
    return 100.0 * scope_seconds(ctx["summary"].op_s, names, scope) / busy
