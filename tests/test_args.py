"""A flag the worker's parser declares is forwarded by the master and
read by the worker.

`worker_forward_args` builds a worker's argv from the master's args. A
flag can be declared, forwarded and parsed and still do nothing: the
worker's `main` has to read it. The cases are the worker's parser's own
flags, so a new flag is held to all of it without a row here.
"""

import ast
import inspect

import pytest

from elasticdl_tpu.common.args import (
    master_parser,
    worker_forward_args,
    worker_parser,
)
from elasticdl_tpu.worker import main as worker_main

# `--use_async`: the pipeline's depth is forwarded as resolved, and a
# synchronous job clamps it to its staleness window
_MASTER_ARGV = [
    "--model_zoo", "zoo", "--model_def", "m.f", "--minibatch_size", "8",
    "--use_async",
]
_WORKER_FLAGS = {
    a.dest: a for a in worker_parser()._actions if a.dest != "help"
}


def _off_default(action):
    """A value the flag's parser accepts that is not its default."""
    if action.choices:
        return [c for c in action.choices if c != action.default][-1]
    if action.type is not None:
        return action.type("3")
    return f"some_{action.dest}"


def _flags_read_by(module) -> set:
    """Every `args.<flag>` and `getattr(args, "<flag>", ...)` in the
    module's source."""
    read = set()
    for node in ast.walk(ast.parse(inspect.getsource(module))):
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "args"
        ):
            read.add(node.attr)
        elif (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "getattr"
            and len(node.args) >= 2
            and isinstance(node.args[0], ast.Name)
            and node.args[0].id == "args"
            and isinstance(node.args[1], ast.Constant)
        ):
            read.add(node.args[1].value)
    return read


@pytest.mark.parametrize("flag", sorted(_WORKER_FLAGS))
def test_a_worker_flag_is_forwarded_parsed_and_read(flag):
    value = _off_default(_WORKER_FLAGS[flag])
    # the worker's id and the master's address are the call's own
    # arguments; a flag the master's parser has not (the candidates,
    # put there by whoever starts a standby master) is set on its args
    call = {"worker_id": 7, "master_addr": "localhost:5001"}
    master = master_parser()
    if flag in call:
        call[flag] = value
        args = master.parse_args(_MASTER_ARGV)
    elif f"--{flag}" in master._option_string_actions:
        args = master.parse_args(_MASTER_ARGV + [f"--{flag}", str(value)])
    else:
        args = master.parse_args(_MASTER_ARGV)
        setattr(args, flag, value)
    argv = worker_forward_args(args, **call)
    assert f"--{flag}" in argv
    assert getattr(worker_parser().parse_args(argv), flag) == value
    assert flag in _flags_read_by(worker_main)
