"""What a compiled program says of itself: {instruction name: op_name}
of every HLO instruction, and the executable's memory analysis.

A device trace names an operation by its HLO instruction (on the v5e
the event's name is the instruction's whole text, `%fusion.12 = ...`,
with no metadata and no stat beside its times), so the
`jax.named_scope` an operation was traced under is not in the trace.
It is in the compiled module: every instruction's
`metadata={op_name="jit(window)/.../jvp(looped_stack)/.../attention/dot_general"}`.
A worker that has a log directory writes, after the first call of
each jitted program of its training path, every instruction's
`op_name` and `compiled.memory_analysis()` to
`$EDL_WORKER_LOG_DIR/worker-<id>.hlo_scopes.json`; a reader of the
trace joins an event to its `op_name` on (program, instruction name)
and decides itself which scopes it looks for.

The names are the executable's, and an executable served by the
persistent compile cache carries the names of the trace that compiled
it: jax leaves metadata out of the cache's key unless asked
(`common/args.py` asks). `describe` therefore holds the compiled text
against the lowering of this call, whose locations are this trace's:
`stale` says the lowering names a scope the executable does not, and
a reader that meets it reads nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Iterable, Set

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M,
)
# every instruction of the text, named or not: `%fusion.1 = f32[...`
_ANY_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = ", re.M)
# a Pallas kernel in the compiled text: Mosaic's custom call
_KERNEL = 'custom_call_target="tpu_custom_call"'
# a name location of the lowering's debug text, `loc("jvp(head)/mul"(#loc3))`;
# a file location is `loc("/path/file.py":12:3)`
_NAME_LOCATION = re.compile(r'loc\("([^"]+)"[()]')
_JIT = re.compile(r"p?jit\([^()]*\)")
# the steps' programs in the order in which one of them is `program`
STEP_PROGRAMS = ("jit_window", "jit_step")
MEMORY_FIELDS = ("argument", "output", "alias", "temp", "generated_code")


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: its op_name} of every instruction of
    `hlo_text` that carries one."""
    return dict(_INSTRUCTION.findall(hlo_text))


def _words(path: str, leaf: bool = False):
    """The scope words of one `op_name`, outermost first: every word of
    every component but the last (with it when `leaf`), `jit(...)`
    left out wherever it stands."""
    parts = _JIT.sub("", path).split("/")
    return [
        w
        for part in (parts if leaf else parts[:-1])
        for w in re.split(r"[()]", part)
        if w
    ]


def scopes(paths: Iterable[str], leaf: bool = False) -> Set[str]:
    """The scopes `paths` run through: every word of every component
    but the last (the primitive's own name; with it when `leaf`), a
    component `transpose(jvp(head))` giving `transpose`, `jvp` and
    `head`. A `jit(...)` is left out wherever it stands: what it wraps
    is a function's name, and the compiler inlines and folds such
    helpers away whole."""
    return {w for path in paths for w in _words(path, leaf)}


def kernels(hlo_text: str) -> Dict[str, int]:
    """{scope: how many Mosaic custom calls (Pallas kernels) of
    `hlo_text` sit under it}, the scope being the innermost one of the
    call's `op_name` (`.../transpose(jvp(attention))/pallas_call` ->
    `attention`; "" for a call with none: the compiler's own Mosaic
    programs, its grouped matmul behind `lax.ragged_dot`, are named
    `ragged-dot-none` and nothing else). {} for a program without a
    kernel: whether a dispatcher engaged its kernels is then read from
    the program, not inferred from a time."""
    found: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if _KERNEL not in line:
            continue
        named = _INSTRUCTION.match(line)
        words = _words(named.group(2)) if named else []
        scope = words[-1] if words else ""
        found[scope] = found.get(scope, 0) + 1
    return found


def memory(compiled) -> Dict[str, int]:
    """`compiled.memory_analysis()` as {field: bytes} ({} where the
    backend gives none)."""
    stats = compiled.memory_analysis()
    if stats is None:
        return {}
    return {
        field: int(getattr(stats, f"{field}_size_in_bytes"))
        for field in MEMORY_FIELDS
    }


def describe(lowered, compiled) -> Dict:
    """One program's record from the lowering and the executable of a
    call jax has already made (`program.lower(*args)` and its
    `.compile()` hand both back): `instructions`, `count` (all the
    text's instructions, named or not), `kernels`, `memory`, and
    `stale` with the scopes `missing` from the executable."""
    text = compiled.as_text()
    instructions = op_names(text)
    traced = scopes(_NAME_LOCATION.findall(lowered.as_text(debug_info=True)))
    missing = sorted(traced - scopes(instructions.values(), leaf=True))
    return {
        "instructions": instructions,
        "count": len(_ANY_INSTRUCTION.findall(text)),
        "kernels": kernels(text),
        "memory": memory(compiled),
        "stale": bool(missing),
        "missing": missing[:10],
    }


def step_program(programs: Dict[str, Dict]) -> str:
    """Which of `programs` trains: the window where there is one,
    else the step, else the first written."""
    for name in STEP_PROGRAMS:
        if name in programs:
            return name
    return next(iter(programs))


def _replace(path: str, record: Dict) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, path)


def write(path: str, program: str, hlo_text: str) -> int:
    """Write the map of one program from its compiled text alone; ->
    the number of instructions."""
    record = {"program": program, "instructions": op_names(hlo_text)}
    _replace(path, record)
    return len(record["instructions"])


def write_programs(path: str, programs: Dict[str, Dict]) -> None:
    """Write the records (`describe`) of all of a worker's programs so
    far. `program` and `instructions` at the top are the step's
    (`step_program`): what the readers that know one program read."""
    primary = step_program(programs)
    _replace(path, {
        "program": primary,
        "instructions": programs[primary]["instructions"],
        "programs": programs,
    })
