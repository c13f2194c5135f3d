"""The five readers of the `laguna-xs2` cell (`swa_attention_pct`,
`global_attention_pct`, `swa_roofline_pct`, `window_moe_pct`,
`attn_gate_mean`) on hand-made planes whose answer is known: leaf
operations joined to their scope on the HLO instruction's name, the
attention kernels of each kind by their `op_name`, the grouped matmuls
by their instruction's name, the banded kernels' calls counted forward
and backward and held to `flops.py`'s roofline; nothing, and no error,
on a run without the scopes."""

import json
import os
import sys
from unittest import mock

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark.harness import flops as harness_flops  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.harness.manifest import load_module  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    _hybrid,
    _moe,
    _timeline,
    _window,
)

US = 1000  # ns
FWD = "jit(window)/while/body/closed_call/jvp()/while/body/closed_call/"
BACK = "jit(window)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/"
INSTRUCTIONS = {
    "while.1": "jit(window)/while",
    "fusion.2": FWD + "attention/swa/dot_general",
    "fusion.3": FWD + "attention/swa/rope/mul",
    "swa.4": FWD + "attention/swa/pallas_call",
    "fusion.5": FWD + "attention/swa/gate/mul",
    "fusion.6": FWD + "attention/global/dot_general",
    "global.7": FWD + "attention/global/pallas_call",
    "fusion.8": FWD + "moe/route/sort",
    "ragged-dot-none.1": "ragged-dot-none",  # the scope is lost
    "fusion.9": FWD + "moe/shared/dot_general",
    "swa.10": BACK + "rematted_computation/attention/swa/pallas_call",
    "swa.11": BACK + "attention/swa/pallas_call",
    "swa.12": BACK + "attention/swa/pallas_call",
    "global.13": BACK + "attention/global/pallas_call",
    # a reduction the compiler set round the recomputed call: it keeps
    # the call's `op_name` and is no call
    "reduce.16": BACK + "rematted_computation/attention/swa/pallas_call",
    "fusion.14": FWD + "mlp/dot_general",
    "fusion.15": "jit(window)/while/body/closed_call/optimizer/add",
}


def text(name, kind="fusion"):
    return f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} %p), kind=kLoop"


# one step inside while.1 [0, 300): a sliding layer's projection 20,
# rotation 10, forward kernel 10, gate 5; a full layer's projection 15
# and kernel 30; the sort 10, a grouped matmul 10, the shared expert 5;
# the recomputed banded forward 10, dq 20 and dk+dv 30; the full
# layer's backward kernel 40; the dense MLP 35, the optimizer 40 (of
# which a stray reduction under the banded call's `op_name` takes the
# last 2 in its stead); 10 of while.1 its own
OPS = [
    (text("while.1", "while"), 0, 300 * US),
    (text("fusion.2"), 0, 20 * US),
    (text("fusion.3"), 20 * US, 30 * US),
    (text("swa.4", "custom-call"), 30 * US, 40 * US),
    (text("fusion.5"), 40 * US, 45 * US),
    (text("fusion.6"), 45 * US, 60 * US),
    (text("global.7", "custom-call"), 60 * US, 90 * US),
    (text("fusion.8"), 90 * US, 100 * US),
    (text("ragged-dot-none.1", "custom-call"), 100 * US, 110 * US),
    (text("fusion.9"), 110 * US, 115 * US),
    (text("swa.10", "custom-call"), 115 * US, 125 * US),
    (text("swa.11", "custom-call"), 125 * US, 145 * US),
    (text("swa.12", "custom-call"), 145 * US, 175 * US),
    (text("global.13", "custom-call"), 175 * US, 215 * US),
    (text("fusion.14"), 215 * US, 250 * US),
    (text("fusion.15"), 250 * US, 288 * US),
    (text("reduce.16"), 288 * US, 290 * US),
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 300 * US)]), ("XLA Ops", OPS)]
CONFIG = os.path.join(ROOT, "benchmark", "configs", "laguna-xs2")
FLOPS = load_module(os.path.join(CONFIG, "flops.py"))
SIZES = load_module(os.path.join(CONFIG, "zoo.py")).SIZES
CELL = "laguna-xs2.window16-serial-1w"
TRACE_READERS = ("swa_attention_pct", "global_attention_pct",
                 "swa_roofline_pct", "window_moe_pct")
READERS = TRACE_READERS + (
    "attn_gate_mean", "window_expert_load_max_over_mean",
)
BAND = 4_063_488


def walk(lo, hi):
    """`_moe.py`'s walk with this cell's table and its reading of a
    grouped matmul."""
    with _hybrid._in_place_of(
        _moe, SHARES=_window.SHARES, shares_of=_window.shares_of
    ):
        return _moe.plane_seconds(LINES, INSTRUCTIONS, lo, hi, ("XLA Ops",))


def test_shares_of_busy_time_by_scope_and_by_kernel_name():
    seconds, busy, _grouped = walk(0, 400 * US)
    assert busy == pytest.approx(300e-6)
    # (the stray reduction is the sliding layers' time, though no call)
    assert seconds["swa"] == pytest.approx(
        (20 + 10 + 10 + 5 + 10 + 20 + 30 + 2) * 1e-6
    )
    assert seconds["global"] == pytest.approx((15 + 30 + 40) * 1e-6)
    # the sort, the grouped matmul by its name, the shared expert
    assert seconds["moe"] == pytest.approx((10 + 10 + 5) * 1e-6)


def test_the_banded_kernels_calls_are_counted_forward_and_backward():
    seconds, forward, backward = _window.banded_calls(
        LINES, INSTRUCTIONS, 0, 400 * US, ("XLA Ops",)
    )
    # the first pass and the recomputation are forward calls; the full
    # layers' kernels are no banded call
    assert (forward, backward) == (2.0, 2.0)
    assert seconds == pytest.approx((10 + 10 + 20 + 30) * 1e-6)
    # the slice's edge cuts dq in half and leaves dk+dv out
    seconds, forward, backward = _window.banded_calls(
        LINES, INSTRUCTIONS, 35 * US, 135 * US, ("XLA Ops",)
    )
    assert (forward, backward) == (1.5, 0.5)
    assert seconds == pytest.approx((5 + 10 + 10) * 1e-6)


@pytest.mark.parametrize("name,path,want", [
    ("fusion.1", FWD + "attention/swa/rope/mul", ("swa",)),
    ("swa.2", BACK + "rematted_computation/attention/swa/pallas_call", ("swa",)),
    ("fusion.1", BACK + "attention/global/gate/mul", ("global",)),
    ("global.3", BACK + "attention/global/pallas_call", ("global",)),
    ("fusion.1", FWD + "moe/route/gather", ("moe",)),
    ("fusion.1", FWD + "moe/cond/branch_1_fun/experts/mul", ("moe",)),
    ("ragged-dot-none.3", "ragged-dot-none", ("moe",)),
    ("ragged-dot-metadata.3", "ragged-dot-metadata", ("moe",)),
    ("fusion.1", FWD + "attention/dot_general", ()),  # another cell's attention
    ("fusion.1", FWD + "swa/dot_general", ()),  # `attention` has to be there
    ("fusion.1", FWD + "mlp/dot_general", ()),  # the dense layer's
    ("fusion.1", None, ()),
])
def test_an_instruction_counts_under_its_scopes_or_by_its_kernel_s_name(
    name, path, want
):
    with _hybrid._in_place_of(
        _moe, SHARES=_window.SHARES, shares_of=_window.shares_of
    ):
        assert _moe.shares_of(name, path) == want


def run_directory(tmp_path, monkeypatch, instructions, spans=()):
    """A run directory whose trace is the hand-made plane."""
    for module in (_window, _hybrid, _moe, _timeline):
        monkeypatch.setattr(module, "_cache", {})
    run_dir = tmp_path / ".bench_runs" / "cell-s1-t1"
    for sub in ("probe", "logs", "tb"):
        (run_dir / sub).mkdir(parents=True)
    (run_dir / "probe" / "trace.latch").write_text("1000.25")
    if instructions is not None:
        (run_dir / "logs" / "worker-0.hlo_scopes.json").write_text(json.dumps(
            {"program": "jit_window", "instructions": instructions}
        ))
    (run_dir / "probe" / "77.json").write_text(json.dumps({
        "worker_id": 0, "kind": "TPU v5 lite",
        "trace": {"state": "written", "dir": str(tmp_path / "trace")},
    }))
    (run_dir / "tb" / "master.spans.jsonl").write_text("")
    with open(run_dir / "logs" / "worker-0.spans.jsonl", "w") as f:
        for ts, args in spans:
            f.write(json.dumps({
                "name": "worker.window_stats", "cat": "phase", "ts": ts,
                "dur": 0.0, "pid": 1, "tid": 1, "args": {**args, "steps": 16},
            }) + "\n")
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(
        trace_reduce, "load", lambda path: [("/device:TPU:0", LINES)]
    )
    monkeypatch.setattr(
        _timeline, "_slice_and_origin", lambda planes, info: ((0, 400 * US), 0)
    )
    (tmp_path / "benchmark" / "configs").mkdir(parents=True)
    os.symlink(CONFIG, tmp_path / "benchmark" / "configs" / SIZES["name"])
    reader = str(tmp_path / "benchmark" / "layer_metrics" / "x.py")
    run = {"platform": "tpu", "trace": {"busy_s": 1.0},
           "window": {"wall0": 1000.3, "wall1": 1045.3},
           "sizes": dict(SIZES),
           "mix": {"master_flags": {"local_updates": 16}}}
    return run, reader


def test_the_walk_is_moe_py_s_own_with_this_cell_s_table(tmp_path, monkeypatch):
    """`trace_seconds` end to end: `_moe.py`'s loop over maps, probes
    and planes with `_window.SHARES` in place, and `_moe.py` left with
    its own table, its own walk and nothing cached."""
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    own, own_shares_of, own_walk = (
        dict(_moe.SHARES), _moe.shares_of, _moe.plane_seconds
    )
    found = _window.trace_seconds(run, reader)
    assert found["busy"] == pytest.approx(300e-6)
    assert found["kind"] == "TPU v5 lite"
    assert (found["forward"], found["backward"]) == (2.0, 2.0)
    assert found["kernel_seconds"] == pytest.approx(70e-6)
    assert _window.share(run, reader, "swa") == pytest.approx(100 * 107 / 300)
    assert _window.share(run, reader, "global") == pytest.approx(100 * 85 / 300)
    assert _window.share(run, reader, "moe") == pytest.approx(100 * 25 / 300)
    assert _moe.SHARES == own and _moe.shares_of is own_shares_of
    assert _moe.plane_seconds is own_walk and _moe._cache == {}
    # the routed cell's reader after it, same process: its own table
    assert _moe.share(run, reader, "route") == pytest.approx(100 * 10 / 300)


def test_the_roofline_credits_the_band_s_pairs_and_cannot_pass_100(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    forward = FLOPS.swa_call_flops(SIZES, FLOPS.FORWARD_PRODUCTS)
    backward = FLOPS.swa_call_flops(SIZES, FLOPS.BACKWARD_PRODUCTS / 2)
    assert forward == 4 * 64 * 128 * BAND == pytest.approx(133.15e9, rel=1e-4)
    assert backward == 7 * 64 * 128 * BAND
    tensor = 2 * 8192 * 64 * 128  # one array of [tokens, heads, 128] in bf16
    assert FLOPS.swa_call_bytes(SIZES, 4) == 4 * tensor
    # both kinds of call are compute-bound on the v5e (240 FLOP/B), by a hair
    assert 240 < forward / (4 * tensor) < 260
    assert 240 < backward / (5.5 * tensor)
    got = _window.swa_roofline(run, reader)
    # two forward and two backward calls in 70 us
    assert got == pytest.approx(
        100 * (2 * forward + 2 * backward) / 197e12 / 70e-6
    )
    # a call that took the least the chip could take reads 100, not more
    found = {"kernel_seconds": forward / 197e12, "forward": 1.0, "backward": 0.0}
    assert _window.roofline_pct(found, SIZES, FLOPS, 197e12, 819e9) == (
        pytest.approx(100.0)
    )
    # where the memory is the roof, the bytes decide
    assert _window.roofline_pct(found, SIZES, FLOPS, 1e18, 819e9) == (
        pytest.approx(100 * (4 * tensor / 819e9) / (forward / 197e12))
    )
    assert _window.roofline_pct(
        {"kernel_seconds": 0.0, "forward": 0.0, "backward": 0.0},
        SIZES, FLOPS, 197e12, 819e9,
    ) is None


def test_the_gate_s_mean_is_the_window_s_spans_mean(tmp_path, monkeypatch):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS, spans=[
        (990.0, {"attn_gate_mean": 0.9}),  # before the window
        (1010.0, {"attn_gate_mean": 0.5}),
        (1020.0, {"attn_gate_mean": 0.4}),
        (1030.0, {"held_share": 0.1}),  # another model's span
    ])
    assert _window.gate_mean(run, reader) == pytest.approx(0.45)
    module = load_module(
        os.path.join(ROOT, "benchmark", "layer_metrics", "attn_gate_mean.py")
    )
    with mock.patch.object(
        _timeline, "find_run_dir",
        lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
    ):
        assert module.read(run) == pytest.approx(0.45)


def test_the_load_ratio_is_the_fullest_held_expert_over_the_mean(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS, spans=[
        (990.0, {"expert_tokens": [[9.0, 1.0]]}),  # before the window
        (1010.0, {"expert_tokens": [[3.0, 1.0], [2.0, 2.0]]}),  # 1.5, 1
        (1020.0, {"expert_tokens": [[4.0, 0.0], [0.0, 0.0]]}),  # 2, none
        (1030.0, {"attn_gate_mean": 0.4}),
    ])
    module = load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "window_expert_load_max_over_mean.py",
    ))
    with mock.patch.object(
        _timeline, "find_run_dir",
        lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
    ):
        assert module.read(run) == pytest.approx((1.5 + 1.0 + 2.0) / 3)


def test_the_configuration_s_flops_by_hand():
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    sliding = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    assert (full, sliding) == (29_458_432, 37_879_808)
    assert FLOPS.attention_macs(SIZES, 48) == full
    assert FLOPS.attention_macs(SIZES, 64) == sliding
    assert FLOPS.score_macs(SIZES, 48) == 2 * 48 * 128 * 33_558_528
    assert FLOPS.score_macs(SIZES, 64, 512) == 2 * 64 * 128 * BAND
    # ISSUE 48's: the two full layers' scores 3 x 2 x 824.7 G, the three
    # sliding layers' 3 x 3 x 133.1 G
    assert 2 * FLOPS.score_macs(SIZES, 48) == pytest.approx(824.7e9, rel=1e-3)
    assert 2 * FLOPS.score_macs(SIZES, 64, 512) == pytest.approx(133.1e9, rel=1e-3)
    assert harness_flops.flops_per_sample(SIZES, CONFIG) == pytest.approx(
        19.396e12, rel=1e-4
    )
    assert FLOPS.swa_heads(SIZES) == 64


@pytest.mark.parametrize("reader", TRACE_READERS)
@pytest.mark.parametrize("run", [
    {"platform": "cpu", "trace": {"busy_s": 1.0}},
    {"platform": "tpu", "trace": None},
], ids=["off-the-tpu", "untraced"])
def test_off_the_tpu_or_untraced_the_trace_readers_say_nothing(reader, run):
    """(`attn_gate_mean` is the program's own span, read wherever it
    was written.)"""
    module = load_module(
        os.path.join(ROOT, "benchmark", "layer_metrics", reader + ".py")
    )
    assert module.read(run) is None


@pytest.mark.parametrize("instructions", [
    None,  # a program that writes no map
    {"while.1": "jit(window)/while", "fusion.2": FWD + "attention/dot_general",
     "custom-call.6": FWD + "attention/pallas_call",
     "fusion.8": FWD + "moe/route/sort"},  # another model's scopes
], ids=["no-map", "other-scopes"])
def test_a_run_without_the_scopes_reads_nothing_and_does_not_raise(
    tmp_path, monkeypatch, instructions
):
    """The parent commit these files are laid over has no `swa` scope
    and writes no `attn_gate_mean` for any cell: None, no error."""
    run, reader = run_directory(
        tmp_path, monkeypatch, instructions,
        spans=[(1010.0, {"held_share": 0.2})],
    )
    for name in ("swa", "global", "moe"):
        assert _window.share(run, reader, name) is None
    assert _window.swa_roofline(run, reader) is None
    assert _window.gate_mean(run, reader) is None
    for name in READERS:
        module = load_module(
            os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        )
        with mock.patch.object(
            _timeline, "find_run_dir",
            lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
        ):
            assert module.read(run) is None


# ------------------------------------------------ the configuration's files


def test_the_configuration_states_its_source_cuts_and_sizes():
    from benchmark.harness import manifest as manifest_lib

    with open(os.path.join(CONFIG, "config.json")) as f:
        sizes = json.load(f)
    for key in ("source", "assumed", "reduced", "published", "deployment",
                "parameters_how", "minibatch_rehearsal", "layer_types",
                "loss_check"):
        assert sizes[key], key
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    published = sizes["published"]
    assert published["num_hidden_layers"] == 40 == len(sizes["layer_types"])
    assert published["num_experts"] == 256 == 16 * sizes["num_experts"]
    assert published["vocab_size"] == 100352 == 8 * sizes["vocab_size"]
    assert sizes["held_experts"] == [0, 16] and "16 chips" in sizes["deployment"]
    # the published patterns whole, and the layers held here: the one
    # dense layer and one whole period
    kinds = sizes["layer_types"]
    assert kinds.count("sliding_attention") == 30
    assert kinds.count("full_attention") == 10
    first, count = sizes["held_layers"]
    assert (first, count) == (0, 5) == (0, sizes["num_hidden_layers"])
    assert kinds[:5] == ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"
    ]
    assert sizes["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert sizes["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    # every number of the catalog's row under its key, but the three cuts
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # the guide's, where this checkout has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "Laguna-XS.2")
        assert sizes["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in sizes["reduced"]:
                assert sizes[key] == value, key
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    sliding = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    rest = 4096 + 2048 * 256 + 17 * 3 * 2048 * 512
    assert (sliding + rest, full + rest) == (91_885_568, 83_464_192)
    assert sizes["parameters"] == (
        full + 4096 + 3 * 2048 * 8192 + 3 * (sliding + rest) + full + rest
        + 2 * 12544 * 2048 + 2048
    ) == 490_297_344
    rehearsal = sizes["minibatch_rehearsal"]["held_16_at_1x8192"]
    assert rehearsal["with_base_flat"] == (
        rehearsal["program_alone"] + 4 * sizes["parameters"]
    ) < 14.5e9
    assert sizes["records_per_task"] == 16 * sizes["minibatch_per_chip"] == 16
    assert sizes["seq_len"] == sizes["data"]["seq_len"] == 8192
    assert sizes["data"]["alphabet"] <= sizes["vocab_size"]
    with open(os.path.join(CONFIG, "zoo.py")) as f:
        assert "probe.start_if_worker()" in f.read()
    with open(os.path.join(CONFIG, "reference.py")) as f:
        source = f.read()
    assert "elasticdl_tpu" not in source and "import benchmark" not in source
    assert "pallas" not in source and "lax.scan" not in source
    committed = manifest_lib.load(ROOT)
    assert manifest_lib.lint(committed, ROOT) == []
    resolved = manifest_lib.resolve(committed, CELL, ROOT)
    assert resolved["cell"]["chips"] == 1
    assert resolved["mix"]["master_flags"] == {
        "local_updates": 16, "grads_to_wait": 1, "overlap_sync": "off"
    }
    assert resolved["config"]["reduced"] == sizes["reduced"]
    assert resolved["config"]["source"] == sizes["source"]
    reported = manifest_lib.cell_metrics(committed, CELL, "per_layer")
    for name in READERS:
        assert reported[name]["workloads"] == [CELL]
        assert reported[name]["moves"] == "goodput"
        assert os.path.isfile(manifest_lib.reader_file(name, ROOT))
    assert reported["swa_roofline_pct"]["better"] == "higher"
    assert reported["swa_roofline_pct"]["layer"] == "kernels"
    assert reported["attn_gate_mean"]["source"] == "program_span"
    assert reported["window_expert_load_max_over_mean"]["better"] == "lower"
    assert "mfu_pct" in reported  # the whole step's share, every cell's
    # found by name, not by place: a later PR appends behind them
    # (ROADMAP R0: three tests that pin the manifest's tail are red)
    names = [m["name"] for m in committed["per_layer"]]
    assert all(names.count(name) == 1 for name in READERS)
    assert CELL in [w["name"] for w in committed["workloads"]]
    assert "laguna-xs2" in [c["name"] for c in committed["configs"]]
    # no other listed metric learned of this cell
    for metric in committed["per_layer"]:
        if metric["name"] not in READERS:
            assert CELL not in metric.get("workloads", ())


def test_compare_py_holds_the_worker_s_own_step_and_the_mixers_to_the_reference(
    tmp_path, monkeypatch
):
    """The script's plumbing at tiny sizes on the CPU (its band is not
    judged there): the float32 program inside `TIGHT`, each kind of
    layer alone telling its controls from the program's own."""
    compare = load_module(os.path.join(CONFIG, "compare.py"))
    monkeypatch.setattr(compare, "ROOT", str(tmp_path))
    assert compare.main(["--seed", "5", "--small"]) == 0
    with open(tmp_path / "chiprun_out" / "laguna_compare.jsonl") as f:
        verdict = json.loads(f.readline())
    assert verdict["float32_beyond_tight"] == {}
    found = verdict["measures"]
    assert found["float32"]["grad_rel_l2"] < 1e-4 < found["timed"]["grad_rel_l2"]
    for mixer, controls in (
        ("swa", ("no_window", "window_513", "no_gate")),
        ("full", ("full_rotary", "no_attention_factor", "no_gate")),
    ):
        own = found["timed"][f"{mixer}_l2"]
        assert found["float32"][f"{mixer}_l2"] < 1e-5 < own < 0.02
        for control in controls:
            assert found[control][f"{mixer}_l2"] > 10 * own, control
    # a control of one kind of layer leaves the other as the program's
    assert found["no_window"]["full_rel"] == found["timed"]["full_rel"]
    assert found["full_rotary"]["swa_rel"] == found["timed"]["swa_rel"]
    assert found["bf16_router"]["router_flips"] > compare.BAND["router_flips"]
    assert found["timed"]["router_flips"] == 0
    assert verdict["programs"]["no_gate"]["attn_gate_mean"] == 1.0
    assert 0.4 < verdict["programs"]["timed"]["attn_gate_mean"] < 0.6
    assert set(compare.BAND) == set(compare.TIGHT)
    assert set(compare.SWAPS) | set(compare.OVERRIDES) == set(compare.CONTROLS)
