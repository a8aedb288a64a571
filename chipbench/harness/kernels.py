"""Which of the program's kernels each Pallas call of a compiled program
runs.

A TPU trace names a Pallas call only by its HLO instruction
(``body.30[tpu_custom_call]``).  The compiled program's HLO text holds
each call's Mosaic body, whose string table keeps the kernel function's
name and the source files of some of the Python frames that built it
(not always the kernel's own: a kernel differentiated under ``jvp``
keeps only its callers').  A call is named by the program's kernel
modules (``repro/kernels/<module>.py``) its body carries, and by the
kernel function names that only one module defines (``KERNEL_NAMES``;
``_kernel`` is the name of five modules' kernels and says nothing).
``ops`` only dispatches to the others and does not count.
"""

from __future__ import annotations

import base64
import re
from typing import Dict, FrozenSet, Iterable, Optional

_CALL = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*'
                   r'custom_call_target="tpu_custom_call"')
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_SOURCE = re.compile(rb"repro/kernels/(\w+)\.py")
_WORD = re.compile(rb"[A-Za-z_][A-Za-z0-9_]*")
DISPATCH_MODULES = frozenset({"ops", "__init__"})
# kernel function names of repro/kernels that one module alone defines
KERNEL_NAMES = {
    "_incr_kernel": "rk_stage",
    "_combine_err_kernel": "rk_stage",
    "_incr_batched_kernel": "rk_stage",
    "_combine_err_batched_kernel": "rk_stage",
    "_combine_err_batched_rowtol_kernel": "rk_stage",
}


class UnidentifiedKernel(RuntimeError):
    """A Pallas call in the trace whose kernel module is not known."""


def body_modules(body_b64: str) -> FrozenSet[str]:
    """Kernel modules one Mosaic body (base64 text) names, by source
    file or by kernel function name."""
    raw = base64.b64decode(body_b64)
    by_file = {m.decode() for m in _SOURCE.findall(raw)}
    by_name = {KERNEL_NAMES[w.decode()] for w in _WORD.findall(raw)
               if w.decode() in KERNEL_NAMES}
    return frozenset(by_file | by_name) - DISPATCH_MODULES


def pallas_calls(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> kernel module of every Pallas call in a
    compiled program's HLO text; None where the body names no kernel
    module or more than one."""
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = _CALL.match(line)
        if not m:
            continue
        b = _BODY.search(line)
        mods = body_modules(b.group(1)) if b else frozenset()
        out[m.group(1)] = next(iter(mods)) if len(mods) == 1 else None
    return out


def merge(maps: Iterable[Dict[str, Optional[str]]]
          ) -> Dict[str, Optional[str]]:
    """One map over several programs; a name that two programs give to
    different kernels is unidentified."""
    out: Dict[str, Optional[str]] = {}
    for m in maps:
        for name, mod in m.items():
            out[name] = mod if out.get(name, mod) == mod else None
    return out


def kernel_seconds(op_s: Dict[str, float],
                   calls: Dict[str, Optional[str]], module: str) -> float:
    """Device seconds of the Pallas calls of ``module`` among the traced
    ops ``op_s`` (short names, ``name[kernel]`` for a custom call).
    Raises ``UnidentifiedKernel`` for a traced Pallas call whose kernel
    is not known, rather than count or drop its time unseen."""
    total = 0.0
    for op, secs in op_s.items():
        if "[" not in op:
            continue
        instr = op.split("[", 1)[0]
        if instr not in calls or calls[instr] is None:
            raise UnidentifiedKernel(
                f"traced Pallas call {op!r} is not one of the window "
                f"programs' identified kernels {sorted(calls)}")
        if calls[instr] == module:
            total += secs
    return total
