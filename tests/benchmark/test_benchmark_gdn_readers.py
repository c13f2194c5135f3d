"""The seven readers of the `qwen3-next-80b-a3b` cell (`gdn_pct`,
`gdn_scan_pct`, `gdn_scan_roofline_pct`, `gated_attention_pct`,
`attn256_roofline_pct`, `next_moe_pct`,
`next_expert_load_max_over_mean`) on hand-made planes whose answer is
known: leaf operations joined to their scope on the HLO instruction's
name, the attention kernels by their `op_name`, the grouped matmuls by
their instruction's name, the passes over the chunks and the causal
kernels' calls counted forward and backward and held to `flops.py`'s
rooflines; nothing, and no error, on a run without the scopes."""

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
    _gdn,
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
    "fusion.2": FWD + "gdn/conv/dot_general",
    "fusion.3": FWD + "gdn/gates/mul",
    "fusion.4": FWD + "gdn/scan/intra/dot_general",
    "while.5": FWD + "gdn/scan/state/while",
    "fusion.6": FWD + "gdn/scan/state/while/body/closed_call/checkpoint/dot_general",
    "fusion.7": FWD + "gdn/out/mul",
    "fusion.8": FWD + "attention/dot_general",
    "attention.9": FWD + "attention/pallas_call",
    "fusion.10": FWD + "attention/gate/mul",
    "fusion.11": FWD + "moe/route/sort",
    "ragged-dot-none.1": "ragged-dot-none",  # the scope is lost
    "fusion.12": FWD + "moe/shared/gate/mul",
    "while.13": BACK + "rematted_computation/gdn/scan/state/while",
    "while.14": BACK + "gdn/scan/state/while",
    "attention.15": BACK + "rematted_computation/attention/pallas_call",
    "attention.16": BACK + "attention/pallas_call",
    "attention.17": BACK + "attention/pallas_call",
    # a reduction the compiler set round a call: its `op_name`, no call
    "reduce.18": BACK + "attention/pallas_call",
    # the blocked stage's own loop is no pass over the chunks
    "while.19": FWD + "gdn/scan/intra/while",
    "fusion.20": "jit(window)/while/body/closed_call/optimizer/add",
}


def text(name, kind="fusion"):
    return f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} %p), kind=kLoop"


# one step inside while.1 [0, 300): a GDN layer's projections 20, gates
# 5, the stage 15 (10 of them inside its own loop), the pass over the
# chunks 30 (its body's product the leaf), the output 5; the attention
# layer's projection 10, forward kernel 20, gate 5; the sort 10, a
# grouped matmul 10, the shared expert's gate 5; the recomputed pass 30
# and the backward pass 50; the recomputed kernel 20, dq 30, dk+dv 40
# with a stray reduction of 2 behind them; the optimizer 3
OPS = [
    (text("while.1", "while"), 0, 300 * US),
    (text("fusion.2"), 0, 20 * US),
    (text("fusion.3"), 20 * US, 25 * US),
    (text("fusion.4"), 25 * US, 30 * US),
    (text("while.19", "while"), 30 * US, 40 * US),
    (text("while.5", "while"), 40 * US, 70 * US),
    (text("fusion.6"), 40 * US, 70 * US),
    (text("fusion.7"), 70 * US, 75 * US),
    (text("fusion.8"), 75 * US, 85 * US),
    (text("attention.9", "custom-call"), 85 * US, 105 * US),
    (text("fusion.10"), 105 * US, 110 * US),
    (text("fusion.11"), 110 * US, 120 * US),
    (text("ragged-dot-none.1", "custom-call"), 120 * US, 130 * US),
    (text("fusion.12"), 130 * US, 135 * US),
    (text("while.13", "while"), 135 * US, 165 * US),
    (text("while.14", "while"), 165 * US, 215 * US),
    (text("attention.15", "custom-call"), 215 * US, 235 * US),
    (text("attention.16", "custom-call"), 235 * US, 265 * US),
    (text("attention.17", "custom-call"), 265 * US, 295 * US),
    (text("reduce.18"), 295 * US, 297 * US),
    (text("fusion.20"), 297 * US, 300 * US),
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 300 * US)]), ("XLA Ops", OPS)]
CONFIG = os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b")
FLOPS = load_module(os.path.join(CONFIG, "flops.py"))
SIZES = load_module(os.path.join(CONFIG, "zoo.py")).SIZES
CELL = "qwen3-next-80b-a3b.window16-serial-1w"
TRACE_READERS = ("gdn_pct", "gdn_scan_pct", "gdn_scan_roofline_pct",
                 "gated_attention_pct", "attn256_roofline_pct", "next_moe_pct")
READERS = TRACE_READERS + ("next_expert_load_max_over_mean",)
TRIANGLE = 33_558_528


def walk(lo, hi):
    with _hybrid._in_place_of(
        _moe, SHARES=_gdn.SHARES, shares_of=_window.shares_of
    ):
        return _moe.plane_seconds(LINES, INSTRUCTIONS, lo, hi, ("XLA Ops",))


def test_shares_of_busy_time_by_scope_and_by_kernel_name():
    seconds, busy, _grouped = walk(0, 400 * US)
    assert busy == pytest.approx(300e-6)
    scan = 5 + 10 + 30 + 30 + 50
    assert seconds["gdn_scan"] == pytest.approx(scan * 1e-6)
    assert seconds["gdn"] == pytest.approx((20 + 5 + scan + 5) * 1e-6)
    # (the stray reduction is the layer's time, though no call)
    assert seconds["attention"] == pytest.approx(
        (10 + 20 + 5 + 20 + 30 + 30 + 2) * 1e-6
    )
    # the sort, the grouped matmul by its name, the shared expert's gate
    assert seconds["moe"] == pytest.approx((10 + 10 + 5) * 1e-6)


@pytest.mark.parametrize("name,path,want", [
    ("fusion.1", FWD + "gdn/scan/intra/mul", ("gdn", "gdn_scan")),
    ("fusion.1", FWD + "gdn/out/mul", ("gdn",)),
    ("attention.2", BACK + "rematted_computation/attention/pallas_call", ("attention",)),
    ("fusion.1", BACK + "attention/gate/mul", ("attention",)),
    ("fusion.1", FWD + "moe/shared/gate/mul", ("moe",)),
    ("ragged-dot-none.3", "ragged-dot-none", ("moe",)),
    ("fusion.1", FWD + "kda/scan/intra/mul", ()),  # the hybrid cell's scan
    ("fusion.1", FWD + "mlp/dot_general", ()),
    ("fusion.1", None, ()),
])
def test_an_instruction_counts_under_its_scopes_or_by_its_kernel_s_name(
    name, path, want
):
    with _hybrid._in_place_of(
        _moe, SHARES=_gdn.SHARES, shares_of=_window.shares_of
    ):
        assert _moe.shares_of(name, path) == want


def run_directory(tmp_path, monkeypatch, instructions, spans=()):
    """A run directory whose trace is the hand-made plane."""
    for module in (_gdn, _window, _hybrid, _moe, _timeline):
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


def test_the_walk_counts_passes_and_calls_and_leaves_the_borrowed_tables(
    tmp_path, monkeypatch
):
    """`trace_seconds` end to end: `_moe.py`'s loop with `_gdn.SHARES`
    in place, `_hybrid.passes` with this cell's path, `_window`'s count
    of calls with `attention` for its scope, and each lender left with
    its own table and nothing cached."""
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    own = (dict(_moe.SHARES), _moe.shares_of, _moe.plane_seconds,
           _hybrid.PASS, dict(_window.SHARES))
    found = _gdn.trace_seconds(run, reader)
    assert found["busy"] == pytest.approx(300e-6)
    assert found["kind"] == "TPU v5 lite"
    # the first pass and the recomputed one forward, one backward; the
    # blocked stage's own loop (`intra/while`) is none
    assert (found["forward"], found["backward"]) == (2.0, 1.0)
    assert (found["kernel_forward"], found["kernel_backward"]) == (2.0, 2.0)
    assert found["kernel_seconds"] == pytest.approx((20 + 20 + 30 + 30) * 1e-6)
    assert _gdn.share(run, reader, "gdn") == pytest.approx(100 * 155 / 300)
    assert _gdn.share(run, reader, "gdn_scan") == pytest.approx(100 * 125 / 300)
    assert _gdn.share(run, reader, "attention") == pytest.approx(100 * 117 / 300)
    assert _gdn.share(run, reader, "moe") == pytest.approx(100 * 25 / 300)
    assert (dict(_moe.SHARES), _moe.shares_of, _moe.plane_seconds,
            _hybrid.PASS, dict(_window.SHARES)) == own
    assert _moe._cache == {}
    # the routed cell's reader after it, same process: its own table
    assert _moe.share(run, reader, "route") == pytest.approx(100 * 10 / 300)


def test_the_scan_s_roofline_credits_the_scalar_form_and_cannot_pass_100(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    one = FLOPS.gdn_scan_flops(8192, SIZES)
    # a token: the two triangles once a key head, the rest a value head
    assert one == 2 * 8192 * (16 * 64 * 64 * 128 + 32 * (
        64 * 64 / 2 * 256 + 3 * 64 * 128 * 128 + 64 * 64 / 2 * 128
    )) / 64 == pytest.approx(34.36e9, rel=1e-3)
    moved = FLOPS.gdn_scan_bytes(8192, SIZES)
    assert moved == 8192 * (2 * (2 * 16 * 128 + 2 * 32 * 128) + 8 * 32)
    roof = min(197e12, 819e9 * one / moved)  # the memory roof: 169 FLOP/B
    assert roof == pytest.approx(819e9 * one / moved) and 160 < one / moved < 180
    got = _gdn.scan_roofline(run, reader)
    # two forward passes and one backward (2 x) in 125 us under gdn/scan
    assert got == pytest.approx(100 * 4 * one / 125e-6 / roof)
    found = {"seconds": {"gdn_scan": one / roof}, "forward": 1.0, "backward": 0.0}
    assert _gdn.scan_roofline_pct(found, 8192, SIZES, FLOPS, 197e12, 819e9) == (
        pytest.approx(100.0)
    )
    found = {"seconds": {"gdn_scan": 0.0}, "forward": 0.0, "backward": 0.0}
    assert _gdn.scan_roofline_pct(found, 8192, SIZES, FLOPS, 197e12, 819e9) is None


def test_the_kernels_roofline_credits_the_triangle_s_pairs_and_cannot_pass_100(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    forward = FLOPS.attention_call_flops(SIZES, FLOPS.FORWARD_PRODUCTS)
    backward = FLOPS.attention_call_flops(SIZES, FLOPS.BACKWARD_PRODUCTS / 2)
    assert forward == 4 * 16 * 256 * TRIANGLE == pytest.approx(549.8e9, rel=1e-3)
    assert backward == 7 * 16 * 256 * TRIANGLE
    tensor = 2 * 8192 * 16 * 256  # one array of [tokens, heads, 256] in bf16
    assert FLOPS.attention_call_bytes(SIZES, 4) == 4 * tensor
    assert forward / (4 * tensor) > 1000  # compute-bound by far
    got = _gdn.attention_roofline(run, reader)
    assert got == pytest.approx(
        100 * (2 * forward + 2 * backward) / 197e12 / 100e-6
    )
    found = {"kernel_seconds": forward / 197e12, "kernel_forward": 1.0,
             "kernel_backward": 0.0}
    assert _gdn.attention_roofline_pct(found, SIZES, FLOPS, 197e12, 819e9) == (
        pytest.approx(100.0)
    )
    found = {"kernel_seconds": 0.0, "kernel_forward": 0.0, "kernel_backward": 0.0}
    assert _gdn.attention_roofline_pct(found, SIZES, FLOPS, 197e12, 819e9) is None


def test_the_load_ratio_is_the_fullest_held_expert_over_the_mean(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS, spans=[
        (990.0, {"expert_tokens": [[9.0, 1.0]]}),  # before the window
        (1010.0, {"expert_tokens": [[3.0, 1.0], [2.0, 2.0]]}),  # 1.5, 1
        (1020.0, {"expert_tokens": [[4.0, 0.0], [0.0, 0.0]]}),  # 2, none
        (1030.0, {"shared_gate_mean": 0.4}),
    ])
    module = load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "next_expert_load_max_over_mean.py",
    ))
    with mock.patch.object(
        _timeline, "find_run_dir",
        lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
    ):
        assert module.read(run) == pytest.approx((1.5 + 1.0 + 2.0) / 3)


def test_the_configuration_s_flops_by_hand():
    assert FLOPS.gdn_mixer_macs(SIZES) == (
        2048 * 12288 + 4 * 8192 + 2048 * 64 + 4096 * 2048
    )
    assert FLOPS.attention_macs(SIZES) == (
        2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    )
    assert FLOPS.score_macs(SIZES) == 2 * 16 * 256 * TRIANGLE
    # ISSUE 52's: the full layer's scores over the triangle 1.65 TFLOP
    assert 6 * FLOPS.score_macs(SIZES) == pytest.approx(1.65e12, rel=2e-3)
    assert harness_flops.flops_per_sample(SIZES, CONFIG) == pytest.approx(
        11.2008e12, rel=1e-4
    )


@pytest.mark.parametrize("reader", TRACE_READERS)
@pytest.mark.parametrize("run", [
    {"platform": "cpu", "trace": {"busy_s": 1.0}},
    {"platform": "tpu", "trace": None},
], ids=["off-the-tpu", "untraced"])
def test_off_the_tpu_or_untraced_the_trace_readers_say_nothing(reader, run):
    module = load_module(
        os.path.join(ROOT, "benchmark", "layer_metrics", reader + ".py")
    )
    assert module.read(run) is None


@pytest.mark.parametrize("instructions", [
    None,  # a program that writes no map
    {"while.1": "jit(window)/while", "fusion.2": FWD + "attention/dot_general",
     "attention.6": FWD + "attention/pallas_call",
     "while.5": FWD + "kda/scan/state/while",
     "fusion.8": FWD + "moe/route/sort"},  # another model's scopes
], ids=["no-map", "other-scopes"])
def test_a_run_without_the_scopes_reads_nothing_and_does_not_raise(
    tmp_path, monkeypatch, instructions
):
    """The parent commit these files are laid over has no `gdn` scope
    for any cell: None, no error."""
    run, reader = run_directory(
        tmp_path, monkeypatch, instructions,
        spans=[(1010.0, {"held_share": 0.2})],
    )
    for name in _gdn.SHARES:
        assert _gdn.share(run, reader, name) is None
    assert _gdn.scan_roofline(run, reader) is None
    assert _gdn.attention_roofline(run, reader) is None
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
    for key in ("source", "assumed", "departures", "reduced", "published",
                "deployment", "parameters_how", "minibatch_rehearsal",
                "layer_types", "loss_check"):
        assert sizes[key], key
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    published = sizes["published"]
    assert published["num_hidden_layers"] == 48 == len(sizes["layer_types"])
    assert published["num_experts"] == 512 == 32 * sizes["num_experts"]
    assert published["vocab_size"] == 151936 == 8 * sizes["vocab_size"]
    assert sizes["held_experts"] == [0, 16] and "32 chips" in sizes["deployment"]
    kinds = sizes["layer_types"]
    assert kinds.count("linear_attention") == 36
    assert kinds.count("full_attention") == 12
    first, count = sizes["held_layers"]
    assert (first, count) == (0, 4) == (0, sizes["num_hidden_layers"])
    assert kinds[:4] == ["linear_attention"] * 3 + ["full_attention"]
    # every number of the catalog's row under its key, but the three cuts
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # the guide's, where this checkout has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert sizes["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in sizes["reduced"]:
                assert sizes[key] == value, key
    linear = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    rest = 4096 + 2048 * 512 + 17 * 3 * 2048 * 512 + 2048
    assert (linear + rest, full + rest) == (88_250_560, 81_795_584)
    assert sizes["parameters"] == (
        3 * (linear + rest) + full + rest + 2 * 18992 * 2048 + 2048
    ) == 424_340_544
    # ISSUE 52's rule: experts 0-7 only if the rehearsal reads over 14.5 GB
    held = sizes["minibatch_rehearsal"]["held_16_at_1x8192"]
    assert held["with_base_flat"] == (
        held["program_alone"] + 4 * sizes["parameters"]
    ) < 14.5e9
    assert sizes["records_per_task"] == 16 * sizes["minibatch_per_chip"] == 16
    assert sizes["seq_len"] == sizes["data"]["seq_len"] == 8192
    assert sizes["data"]["alphabet"] <= sizes["vocab_size"]
    with open(os.path.join(CONFIG, "zoo.py")) as f:
        assert "probe.start_if_worker()" in f.read()
    with open(os.path.join(CONFIG, "reference.py")) as f:
        source = f.read()
    assert "elasticdl_tpu" not in source and "import benchmark" not in source
    assert "pallas" not in source and "cumsum" not in source
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
    for name in ("gdn_scan_roofline_pct", "attn256_roofline_pct"):
        assert reported[name]["better"] == "higher"
        assert reported[name]["layer"] == "kernels"
    assert reported["next_expert_load_max_over_mean"]["better"] == "lower"
    assert reported["next_expert_load_max_over_mean"]["source"] == "program_span"
    assert "mfu_pct" in reported  # the whole step's share, every cell's
    # found by name, not by place: a later PR appends behind them
    # (ROADMAP R0: three tests that pin the manifest's tail are red)
    names = [m["name"] for m in committed["per_layer"]]
    assert all(names.count(name) == 1 for name in READERS)
    assert CELL in [w["name"] for w in committed["workloads"]]
    assert "qwen3-next-80b-a3b" in [c["name"] for c in committed["configs"]]
    # no other listed metric learned of this cell
    for metric in committed["per_layer"]:
        if metric["name"] not in READERS:
            assert CELL not in metric.get("workloads", ())


def test_compare_py_holds_the_worker_s_own_step_and_the_layers_to_the_reference(
    tmp_path, monkeypatch
):
    """The script's plumbing at tiny sizes on the CPU (its band is not
    judged there): the float32 program inside `TIGHT`, each kind of
    layer alone telling its controls from the program's own."""
    compare = load_module(os.path.join(CONFIG, "compare.py"))
    monkeypatch.setattr(compare, "ROOT", str(tmp_path))
    assert compare.main(["--seed", "5", "--small"]) == 0
    with open(tmp_path / "chiprun_out" / "qwen3_next_compare.jsonl") as f:
        verdict = json.loads(f.readline())
    assert verdict["float32_beyond_tight"] == {}
    found = verdict["measures"]
    assert found["float32"]["grad_rel_l2"] < 1e-3 < found["timed"]["grad_rel_l2"]
    for layer, controls in (
        ("gdn", ("no_decay", "sigmoid_z", "key_head_mod", "no_l2")),
        ("full", ("channel_mean_gate", "full_rotary")),
        ("moe", ("no_shared_gate", "no_renormalise")),
    ):
        own = found["timed"][f"{layer}_l2"]
        assert found["float32"][f"{layer}_l2"] < 1e-4 < own < 0.05
        for control in controls:
            assert found[control][f"{layer}_l2"] > 5 * own, control
    # a control of one kind of layer leaves the others as the program's
    assert found["no_decay"]["full_rel"] == found["timed"]["full_rel"]
    assert found["full_rotary"]["gdn_rel"] == found["timed"]["gdn_rel"]
    assert found["bf16_router"]["router_flips"] > compare.BAND["router_flips"]
    assert found["timed"]["router_flips"] == 0
    assert found["timed"]["scan_rel"] < 1e-5 < found["bf16_state"]["scan_rel"]
    assert found["key_head_mod"]["scan_rel"] > 1e-2
    assert verdict["programs"]["no_shared_gate"]["shared_gate_mean"] == 1.0
    assert 0.3 < verdict["programs"]["timed"]["shared_gate_mean"] < 0.7
    assert set(compare.BAND) == set(compare.TIGHT)
    swaps = compare.swaps_for(compare.lm.TransformerConfig(head_width=4))
    assert set(swaps) | set(compare.OVERRIDES) == set(compare.CONTROLS)
