"""From a compiled program's HLO text to {instruction name: op_name}.

A device trace names an operation by its HLO instruction (on the v5e
the event's name is the instruction's whole text, `%fusion.12 = ...`,
with no metadata and no stat beside its times), so the
`jax.named_scope` an operation was traced under is not in the trace.
It is in the compiled module: every instruction's
`metadata={op_name="jit(window)/.../jvp(looped_stack)/.../attention/dot_general"}`.
A worker that is asked to (`EDL_HLO_SCOPES=1`, by whoever takes a
device trace of it) writes every instruction's `op_name` once, after
the window program's first call, to
`$EDL_WORKER_LOG_DIR/worker-<id>.hlo_scopes.json`; a reader of the
trace joins an event to its `op_name` on the instruction's name and
decides itself which scopes it looks for.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M,
)


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: its op_name} of every instruction of
    `hlo_text` that carries one."""
    return dict(_INSTRUCTION.findall(hlo_text))


def write(path: str, program: str, hlo_text: str) -> int:
    """Write the map of one program; -> the number of instructions."""
    record = {"program": program, "instructions": op_names(hlo_text)}
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, path)
    return len(record["instructions"])
