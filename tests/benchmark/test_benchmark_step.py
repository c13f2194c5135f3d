"""The step's readers (`_step.py`: `step_*_pct`, `lm_*_pct`,
`resnet_*_pct`, `program_temp_gb`): every busy moment of the slice in
one phase, joined on (program, instruction) — on hand-made planes
whose answer is known, with paths taken from a program jax compiled
here; and the eleven entries in the committed manifest."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark.harness import manifest  # noqa: E402
from benchmark.layer_metrics import _step  # noqa: E402
from elasticdl_tpu.obs import hlo_scopes  # noqa: E402
from test_benchmark_timeline import (  # noqa: E402,F401
    checkout, make_run, read, write_run,
)

US = 1000  # ns
NEW = (
    "step_forward_pct", "step_backward_pct", "step_optimizer_pct",
    "step_unnamed_pct", "step_recompute_pct", "lm_attention_pct",
    "lm_mlp_pct", "lm_head_pct", "resnet_conv_pct", "resnet_norm_pct",
    "program_temp_gb",
)
PHASES = ("forward", "recompute", "backward", "optimizer", "unnamed")


@pytest.fixture(scope="module")
def toy():
    """`describe` of a window as the worker builds it, small: a scan
    over steps of value_and_grad through a scanned, checkpointed layer,
    then an optax update under `optimizer`."""
    import jax
    import jax.numpy as jnp
    import optax

    tx = optax.adam(1e-3)

    def loss_fn(p, x):
        with jax.named_scope("embed"):
            h = p["e"][x]

        def layer(h, w):
            with jax.named_scope("attention"):
                h = h + jnp.tanh(h @ w)
            with jax.named_scope("mlp"):
                h = h + jax.nn.gelu(h @ w)
            return h, None

        h, _ = jax.lax.scan(jax.checkpoint(layer), h, p["w"])
        with jax.named_scope("head"):
            return jnp.mean((h @ p["o"]) ** 2)

    def window(p, s, xs):
        def body(carry, x):
            p, s = carry
            loss, g = jax.value_and_grad(loss_fn)(p, x)
            with jax.named_scope("optimizer"):
                u, s = tx.update(g, s, p)
                p = jax.tree_util.tree_map(lambda a, b: a + b, p, u)
            return (p, s), loss

        (p, s), losses = jax.lax.scan(body, (p, s), xs)
        return p, s, losses[-1]

    p = {"e": jnp.ones((16, 8)), "w": jnp.ones((3, 8, 8)), "o": jnp.ones((8, 4))}
    lowered = jax.jit(window).lower(p, tx.init(p), jnp.zeros((4, 5), jnp.int32))
    return hlo_scopes.describe(lowered, lowered.compile())


def test_every_phase_is_met_by_a_path_jax_really_writes(toy):
    assert toy["stale"] is False
    by_phase = {}
    for name, path in toy["instructions"].items():
        by_phase.setdefault(_step.phase(path), []).append(path)
    assert set(by_phase) == set(PHASES)
    assert all("rematted_computation" in p for p in by_phase["recompute"])
    assert all("transpose(jvp(" in p for p in by_phase["backward"])
    assert not any("transpose" in p for p in by_phase["forward"])
    assert all("/optimizer" in p for p in by_phase["optimizer"])
    # the blocks, whatever the phase
    for block, phases in (("attention", PHASES[:3]), ("head", ("forward", "backward"))):
        for ph in phases:
            assert any(block in _step.blocks(p) for p in by_phase[ph]), (block, ph)
    assert _step.phase(None) == "unnamed" and _step.blocks(None) == ()


@pytest.mark.parametrize("path, want", [
    ("jit(window)/while/body/closed_call/jvp(ResNet50)/Bottleneck_3/Conv_1/"
     "conv_general_dilated", ("conv",)),
    ("transpose(jvp(ResNet50))/BatchNorm_0/reduce_sum", ("norm",)),
    ("jit(step)/jvp(ResNet50)/Bottleneck_0/max", ()),
    ("jit(window)/while/body/closed_call/jvp(embed)/gather", ("head",)),
    ("jit(window)/while/body/closed_call/transpose(jvp(head))/dot_general",
     ("head",)),
    ("jit(window)/while/body/jvp()/while/body/closed_call/flash_attention/dot",
     ()),  # a scope is a whole word
    ("jit(window)/while/body/closed_call/exit_heads/head/dot_general", ("head",)),
])
def test_a_block_is_a_whole_word_of_the_path(path, want):
    assert _step.blocks(path) == want


def text(name):
    return f"%{name} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p), kind=kLoop"


def planes(toy):
    """One window [0, 100) us and a `jit_subtract` [100, 130) us whose
    one operation bears the name of the window's forward one. The
    window is a `while.9` over one operation of each phase, 10 us
    each, with 5 us of the loop's own between them and 25 at its end."""
    pick = {}
    for name, path in toy["instructions"].items():
        pick.setdefault(_step.phase(path), name)
    assert "while.9" not in toy["instructions"]
    ops, t = [(text("while.9"), 0, 100 * US)], 0
    for ph in PHASES:
        ops.append((text(pick[ph]), t * US, (t + 10) * US))
        t += 15
    ops.append((text(pick["forward"]), 100 * US, 130 * US))
    lines = [
        ("XLA Modules", [("jit_window(7)", 0, 100 * US),
                         ("jit_subtract(8)", 100 * US, 130 * US)]),
        ("XLA Ops", ops),
    ]
    scope_map = {
        "program": "jit_window",
        "instructions": toy["instructions"],
        "programs": {"jit_window": toy},
    }
    return lines, scope_map, pick


def test_two_programs_that_share_a_name_are_kept_apart_and_the_phases_sum(toy):
    lines, scope_map, pick = planes(toy)
    found = _step.plane_seconds(lines, scope_map, 0, 200 * US, ("XLA Ops",))
    assert found["busy"] == pytest.approx(130e-6)
    # the same instruction name: forward in the window, `other` outside
    assert found["phases"]["forward"] == pytest.approx(10e-6)
    assert found["phases"]["other"] == pytest.approx(30e-6)
    for ph in ("recompute", "backward", "optimizer"):
        assert found["phases"][ph] == pytest.approx(10e-6)
    # `while.9` is not in the map: its own 50 us are unnamed, with the
    # unnamed operation's 10
    assert found["phases"]["unnamed"] == pytest.approx(60e-6)
    assert found["unnamed"] == {
        "while.9": pytest.approx(50e-6), pick["unnamed"]: pytest.approx(10e-6),
    }
    assert found["programs"] == {
        "jit_window": pytest.approx(100e-6), "jit_subtract": pytest.approx(30e-6),
    }
    # the phases of the program that trains sum to its busy time, and
    # with the other programs' to the slice's
    assert sum(found["phases"].values()) == pytest.approx(found["busy"])
    assert sum(
        v for k, v in found["phases"].items() if k != "other"
    ) == pytest.approx(found["programs"]["jit_window"])


def test_the_slice_clips_every_phase_and_the_busy_time_alike(toy):
    lines, scope_map, _pick = planes(toy)
    found = _step.plane_seconds(lines, scope_map, 5 * US, 110 * US, ("XLA Ops",))
    assert found["busy"] == pytest.approx(105e-6)
    assert found["phases"]["forward"] == pytest.approx(5e-6)
    assert found["phases"]["other"] == pytest.approx(10e-6)
    assert sum(found["phases"].values()) == pytest.approx(found["busy"])


def test_a_map_of_one_program_alone_is_read_as_that_program_s(toy):
    """What a program before `programs` wrote (`hlo_scopes.write`)."""
    lines, scope_map, _pick = planes(toy)
    old = {"program": "jit_window", "instructions": toy["instructions"]}
    assert _step.plane_seconds(lines, old, 0, 200 * US, ("XLA Ops",)) == (
        _step.plane_seconds(lines, scope_map, 0, 200 * US, ("XLA Ops",))
    )


def test_own_time_is_the_innermost_event_s_and_sums_to_the_union():
    events = [("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("d", 120, 130)]
    own = _step.exclusive(events)
    assert {e[0]: ns for e, ns in own.items()} == {"a": 70, "b": 20, "c": 10, "d": 10}
    assert _step.exclusive([]) == {}


def _run_dir(tmp_path, scope_map=None):
    run_dir = tmp_path / ".bench_runs" / "cell-s1-t1"
    (run_dir / "probe").mkdir(parents=True)
    (run_dir / "logs").mkdir()
    (run_dir / "probe" / "trace.latch").write_text("1000.25")
    if scope_map is not None:
        (run_dir / "logs" / "worker-0.hlo_scopes.json").write_text(
            json.dumps(scope_map)
        )
    return str(tmp_path / "benchmark" / "layer_metrics" / "step_forward_pct.py")


RUN = {"platform": "tpu", "trace": {"busy_s": 1.0}, "window": {"wall0": 1000.3}}


def test_a_run_without_a_map_reads_zero_and_does_not_raise(
    tmp_path, monkeypatch, capsys
):
    """A program that writes no map (the parent commit these files are
    laid over, whose traced line `run.py` refuses if a metric of its
    cell is missing): every reader gives 0 and says nothing was read."""
    reader = _run_dir(tmp_path)
    monkeypatch.setattr(_step, "_cache", {})
    assert _step.read(RUN, reader) == _step.nothing()
    assert _step.phase_pct(RUN, reader, "forward") == 0.0
    assert _step.block_pct(RUN, reader, "conv") == 0.0
    assert _step.temp_gb(RUN, reader) == 0.0
    said = capsys.readouterr().err
    assert "nothing to read" in said and "reads 0" in said


def test_a_stale_map_reads_zero_and_says_why(tmp_path, monkeypatch, capsys, toy):
    stale = {**toy, "stale": True, "missing": ["attention"]}
    reader = _run_dir(tmp_path, {
        "program": "jit_window", "instructions": toy["instructions"],
        "programs": {"jit_window": stale},
    })
    monkeypatch.setattr(_step, "_cache", {})
    assert _step.read(RUN, reader) == _step.nothing()
    assert _step.block_pct(RUN, reader, "attention") == 0.0
    assert _step.temp_gb(RUN, reader) == 0.0  # not the stale map's
    said = capsys.readouterr().err
    assert "stale" in said and "attention" in said and "reads 0" in said


@pytest.mark.parametrize("run", [
    {"platform": "cpu", "trace": {"busy_s": 1.0}},
    {"platform": "tpu", "trace": None},
])
def test_off_the_tpu_or_untraced_the_readers_say_nothing(run):
    for name in NEW:
        assert manifest.load_module(
            manifest.reader_file(name, ROOT)
        ).read(run) is None


def test_the_committed_manifest_ends_with_the_eleven_and_lints_clean():
    committed = manifest.load(ROOT)
    assert manifest.lint(committed, ROOT) == []
    tail = committed["per_layer"][-11:]
    assert tuple(m["name"] for m in tail) == NEW
    cells = [w["name"] for w in committed["workloads"]]
    for m in tail:
        assert m["layer"] == "worker step" and m["moves"] == "goodput"
        assert set(m["workloads"]) <= set(cells) and m["workloads"]
        assert os.path.isfile(manifest.reader_file(m["name"], ROOT))
        assert m["source"] == (
            "program_counter" if m["name"] == "program_temp_gb" else "device_trace"
        )
    listed = {m["name"]: m["workloads"] for m in tail}
    for name in NEW[:4] + NEW[-1:]:
        assert listed[name] == cells
    assert listed["step_recompute_pct"] == [
        "ouro-2.6b.window16-1w", "deepseek-v2-lite.window16-serial-1w",
    ]
    assert listed["lm_attention_pct"] == ["lm-dense-160m.window-1w"]
    assert listed["resnet_norm_pct"] == [
        "resnet50-224.window-1w", "resnet50-224.perstep-1w",
    ]


def test_the_readers_read_a_recorded_trace_end_to_end(checkout, capsys):
    """The committed fixture trace (`xplane_fixture.py`) laid out as
    the probe leaves it, with a map beside the worker's spans: chip 0's
    window holds fusion.1, while.2 and fusion.3, its `jit_copy` a second
    fusion.1; chip 1 has no `XLA Modules` line at all."""
    run_dir = write_run(checkout)
    window = {
        "instructions": {
            "fusion.1": "jit(window)/while/body/closed_call/jvp(embed)/gather",
            "fusion.3": "jit(window)/while/body/closed_call/optimizer/add",
        },
        "count": 3, "stale": False, "missing": [],
        "memory": {"argument": 1, "output": 1, "alias": 1,
                   "temp": 2_500_000_000, "generated_code": 1},
    }
    with open(os.path.join(run_dir, "logs", "worker-0.hlo_scopes.json"), "w") as f:
        json.dump({"program": "jit_window", "programs": {"jit_window": window},
                   "instructions": window["instructions"]}, f)
    _step._cache.clear()
    run = make_run()
    # busy: chip 0 2000 + 4000 + 1000 us, chip 1 3000 us
    assert read(checkout, "step_forward_pct", run) == pytest.approx(20.0)
    assert read(checkout, "step_optimizer_pct", run) == pytest.approx(20.0)
    assert read(checkout, "step_unnamed_pct", run) == pytest.approx(20.0)  # while.2's own
    assert read(checkout, "step_backward_pct", run) == 0.0
    assert read(checkout, "step_recompute_pct", run) == 0.0
    assert read(checkout, "lm_head_pct", run) == pytest.approx(20.0)
    assert read(checkout, "lm_attention_pct", run) == 0.0
    assert read(checkout, "program_temp_gb", run) == pytest.approx(2.5)
    found = _step.read(run, manifest.reader_file("lm_mlp_pct", checkout))
    assert found["phases"]["other"] == pytest.approx(4000e-6)
    assert sum(found["phases"].values()) == pytest.approx(found["busy"])
    # the union `device_idle_pct` has, chip by chip
    assert found["busy"] == pytest.approx(
        sum(run["trace"]["busy_s_by_chip"].values())
    )
    said = capsys.readouterr().err
    assert "step: busy 0.0100s" in said and "longest unnamed: while.2" in said
    assert "jit_window 0.0060s" in said and "jit_copy 0.0010s" in said
    # a map of one program alone, as a parent commit writes it where its
    # `zoo.py` asks: the phases are read, the temporaries read 0
    with open(os.path.join(run_dir, "logs", "worker-0.hlo_scopes.json"), "w") as f:
        json.dump({"program": "jit_window",
                   "instructions": window["instructions"]}, f)
    _step._cache.clear()
    assert read(checkout, "step_optimizer_pct", run) == pytest.approx(20.0)
    assert read(checkout, "program_temp_gb", run) == 0.0
    assert "`program_temp_gb` reads 0" in capsys.readouterr().err
