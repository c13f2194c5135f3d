"""The scope readers (`loop_stack_pct`, `loop_attention_pct`,
`exit_head_pct`): leaf operations only, joined to their scope on the
HLO instruction's name, as a share of the slice's device-busy time —
on hand-made planes whose answer is known; and the map itself, from a
compiled program's text."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark.layer_metrics import _scopes  # noqa: E402
from elasticdl_tpu.obs import hlo_scopes  # noqa: E402

US = 1000  # ns
STACK = "jit(window)/while/body/jvp(looped_stack)/while/body/closed_call/"
BACK = "jit(window)/while/body/transpose(jvp(looped_stack))/while/body/"
INSTRUCTIONS = {
    "while.1": "jit(window)/while",
    "while.2": "jit(window)/while/body/jvp(looped_stack)/while",
    "fusion.3": STACK + "attention/dot_general",
    "fusion.4": STACK + "mlp/dot_general",
    "fusion.5": BACK + "checkpoint/rematted_computation/attention/exp",
    "fusion.6": "jit(window)/while/body/jvp(exit_heads)/dot_general",
    "fusion.7": "jit(window)/while/body/add",  # the optimizer: no scope
}


def text(name):
    return f"%{name} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p), kind=kLoop"


# one step: while.1 [0, 100) holds while.2 [0, 60) (fusion.3 [0, 20),
# fusion.4 [20, 50), fusion.5 [50, 60)), fusion.6 [60, 80) and
# fusion.7 [80, 90); [90, 100) of while.1 is its own time (no leaf)
OPS = [
    (text("while.1"), 0, 100 * US),
    (text("while.2"), 0, 60 * US),
    (text("fusion.3"), 0, 20 * US),
    (text("fusion.4"), 20 * US, 50 * US),
    (text("fusion.5"), 50 * US, 60 * US),
    (text("fusion.6"), 60 * US, 80 * US),
    (text("fusion.7"), 80 * US, 90 * US),
    (text("copy.9"), 150 * US, 160 * US),  # outside any map: busy, no scope
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 100 * US)]), ("XLA Ops", OPS)]


def test_leaves_are_the_events_that_hold_no_other():
    names = [_scopes.instruction(n) for n, _s, _e in _scopes.leaves(OPS)]
    assert names == [
        "fusion.3", "fusion.4", "fusion.5", "fusion.6", "fusion.7", "copy.9"
    ]
    assert _scopes.leaves([]) == []


def test_shares_of_busy_time_over_the_whole_slice():
    seconds, busy = _scopes.plane_shares(
        LINES, INSTRUCTIONS, 0, 200 * US, ("XLA Ops",)
    )
    assert busy == pytest.approx(110e-6)  # while.1's 100 and copy.9's 10
    assert seconds["stack"] == pytest.approx(60e-6)
    assert seconds["attention"] == pytest.approx(30e-6)  # forward and backward
    assert seconds["exit_heads"] == pytest.approx(20e-6)


def test_the_slice_clips_leaves_and_busy_time_alike():
    seconds, busy = _scopes.plane_shares(
        LINES, INSTRUCTIONS, 10 * US, 70 * US, ("XLA Ops",)
    )
    assert busy == pytest.approx(60e-6)
    assert seconds["stack"] == pytest.approx(50e-6)
    assert seconds["attention"] == pytest.approx(20e-6)
    assert seconds["exit_heads"] == pytest.approx(10e-6)


def test_a_scope_is_a_whole_segment_of_the_path():
    assert _scopes._passes("jit(f)/jvp(looped_stack)/attention/dot", ("attention",))
    assert _scopes._passes(
        "jit(f)/transpose(jvp(looped_stack))/mlp/dot", ("looped_stack",)
    )
    assert not _scopes._passes("jit(f)/flash_attention/dot", ("attention",))
    assert not _scopes._passes(
        "jit(f)/jvp(exit_heads)/dot", ("looped_stack", "attention")
    )


@pytest.mark.parametrize("run", [
    {"platform": "cpu", "trace": {"busy_s": 1.0}},
    {"platform": "tpu", "trace": None},
])
def test_off_the_tpu_or_untraced_the_readers_say_nothing(run):
    from benchmark.layer_metrics import (
        exit_head_pct, loop_attention_pct, loop_stack_pct,
    )

    for reader in (loop_stack_pct, loop_attention_pct, exit_head_pct):
        assert reader.read(run) is None


def test_a_run_without_a_map_reads_nothing_and_does_not_raise(tmp_path, monkeypatch):
    """A program that writes no map (the parent commit these files are
    laid over): None, so the line leaves the metrics out."""
    run_dir = tmp_path / ".bench_runs" / "cell-s1-t1"
    (run_dir / "probe").mkdir(parents=True)
    (run_dir / "logs").mkdir()
    (run_dir / "probe" / "trace.latch").write_text("1000.25")
    reader = tmp_path / "benchmark" / "layer_metrics" / "loop_stack_pct.py"
    run = {"platform": "tpu", "trace": {"busy_s": 1.0},
           "window": {"wall0": 1000.3}}
    monkeypatch.setattr(_scopes, "_cache", {})
    assert _scopes.read(run, str(reader), "stack") is None


def test_the_map_comes_from_the_compiled_module_s_metadata(tmp_path):
    import jax
    import jax.numpy as jnp

    def f(w, x):
        def body(h, _):
            with jax.named_scope("attention"):
                h = jnp.tanh(h @ w)
            return h, None

        with jax.named_scope("looped_stack"):
            h, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=3)
        with jax.named_scope("exit_heads"):
            return jnp.sum(h @ w)

    w, x = jnp.ones((8, 8)), jnp.ones((4, 8))
    compiled = jax.jit(jax.grad(f)).lower(w, x).compile().as_text()
    found = hlo_scopes.op_names(compiled)
    # every instruction that carries an op_name, scoped or not: which
    # scopes count is the reader's to say (`_scopes._passes`)
    assert any(
        not _scopes._passes(p, (scope,))
        for p in found.values() for scope in ("looped_stack", "exit_heads")
    )
    assert any(_scopes._passes(p, ("looped_stack", "attention")) for p in found.values())
    assert any(_scopes._passes(p, ("exit_heads",)) for p in found.values())
    assert any("transpose(jvp(looped_stack))" in p for p in found.values())
    path = tmp_path / "worker-0.hlo_scopes.json"
    count = hlo_scopes.write(str(path), "jit_window", compiled)
    record = json.loads(path.read_text())
    assert record == {"program": "jit_window", "instructions": found}
    assert count == len(found)
