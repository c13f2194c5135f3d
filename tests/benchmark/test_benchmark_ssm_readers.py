"""The seven readers of the `nemotron-3-nano-30b-a3b` cell (`ssm_pct`,
`ssm_scan_pct`, `ssm_scan_roofline_pct`, `nano_attention_pct`,
`nano_moe_pct`, `relu2_experts_roofline_pct`,
`nano_expert_load_max_over_mean`) on hand-made planes whose answer is
known: leaf operations joined to their scope on the HLO instruction's
name, the grouped matmuls by their instruction's name, the scan's
passes counted run by run from its own operations (no `while` to
count), forward and backward, and held to `flops.py`'s rooflines;
nothing, and no error, on a run without the scopes; the manifest's new
entries found by name; `flops.py`'s count by hand."""

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
from benchmark.harness import manifest as manifest_lib  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.harness.manifest import load_module  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    _hybrid,
    _moe,
    _shortconv,
    _ssm,
    _timeline,
)

US = 1000  # ns
FWD = "jit(window)/while/body/closed_call/jvp()/while/body/closed_call/"
BACK = "jit(window)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/"
REMAT = BACK + "rematted_computation/"
INSTRUCTIONS = {
    "while.1": "jit(window)/while",
    # run 0's scan, forward: three instructions, each once a pass
    "fusion.2": FWD + "mamba2/run0/in_proj/dot_general",
    "fusion.3": FWD + "mamba2/run0/scan/intra/dot_general",
    "fusion.4": FWD + "mamba2/run0/scan/state/dot_general",
    "fusion.5": FWD + "mamba2/run0/scan/out/add",
    "fusion.6": FWD + "mamba2/run0/gate_norm/mul",
    # run 1's scan, forward: another copy of the same code
    "fusion.7": FWD + "mamba2/run1/scan/intra/dot_general",
    "fusion.8": FWD + "mamba2/run1/scan/state/dot_general",
    "fusion.9": FWD + "attention/dot_general",
    "attention.10": FWD + "attention/pallas_call",
    "fusion.11": FWD + "moe/route/sort",
    "ragged-dot-none.1": "ragged-dot-none",  # the scope is lost
    "fusion.12": FWD + "moe/shared/dot_general",
    # run 0's scan recomputed, then transposed
    "fusion.13": REMAT + "mamba2/run0/scan/intra/dot_general",
    "fusion.14": REMAT + "mamba2/run0/scan/state/dot_general",
    "fusion.15": BACK + "mamba2/run0/scan/state/dot_general",
    "fusion.16": BACK + "mamba2/run0/scan/intra/dot_general",
    "fusion.17": "jit(window)/while/body/closed_call/optimizer/add",
}


def text(name, kind="fusion"):
    return f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} %p), kind=kLoop"


# one step inside while.1 [0, 300). Run 0 holds TWO layers, so each of
# its forward instructions runs twice: projections 2 x 10, intra 2 x 10,
# state 2 x 5, out 2 x 5, the gate 2 x 5; run 1's one layer intra 10,
# state 5; the attention block's projection 10 and kernel 20; the sort
# 10, a grouped matmul 20, the shared expert 10; run 0 recomputed once
# (intra 10, state 5) and transposed once (state 10, intra 20); the
# optimizer 5; the rest idle
OPS = [
    (text("while.1", "while"), 0, 300 * US),
    (text("fusion.2"), 0, 10 * US), (text("fusion.3"), 10 * US, 20 * US),
    (text("fusion.4"), 20 * US, 25 * US), (text("fusion.5"), 25 * US, 30 * US),
    (text("fusion.6"), 30 * US, 35 * US),
    (text("fusion.2"), 35 * US, 45 * US), (text("fusion.3"), 45 * US, 55 * US),
    (text("fusion.4"), 55 * US, 60 * US), (text("fusion.5"), 60 * US, 65 * US),
    (text("fusion.6"), 65 * US, 70 * US),
    (text("fusion.7"), 70 * US, 80 * US), (text("fusion.8"), 80 * US, 85 * US),
    (text("fusion.9"), 85 * US, 95 * US),
    (text("attention.10", "custom-call"), 95 * US, 115 * US),
    (text("fusion.11"), 115 * US, 125 * US),
    (text("ragged-dot-none.1", "custom-call"), 125 * US, 145 * US),
    (text("fusion.12"), 145 * US, 155 * US),
    (text("fusion.13"), 155 * US, 165 * US), (text("fusion.14"), 165 * US, 170 * US),
    (text("fusion.15"), 170 * US, 180 * US), (text("fusion.16"), 180 * US, 200 * US),
    (text("fusion.17"), 200 * US, 205 * US),
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 300 * US)]), ("XLA Ops", OPS)]
SCAN_US = 2 * (10 + 5 + 5) + 10 + 5 + 10 + 5 + 10 + 20  # under mamba2/scan
SSM_US = SCAN_US + 2 * 10 + 2 * 5
BUSY_US = 300  # `while.1` covers the window
CONFIG = os.path.join(ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b")
FLOPS = load_module(os.path.join(CONFIG, "flops.py"))
with open(os.path.join(CONFIG, "config.json")) as _f:
    SIZES = json.load(_f)
CELL = "nemotron-3-nano-30b-a3b.window16-serial-1w"
TRACE_READERS = ("ssm_pct", "ssm_scan_pct", "ssm_scan_roofline_pct",
                 "nano_attention_pct", "nano_moe_pct",
                 "relu2_experts_roofline_pct")
READERS = TRACE_READERS + ("nano_expert_load_max_over_mean",)


def test_the_scan_s_passes_are_counted_run_by_run_from_its_own_operations():
    count = lambda lo, hi: _ssm.scan_passes(  # noqa: E731
        LINES, INSTRUCTIONS, lo, hi, ("XLA Ops",)
    )
    # forward: run 0 twice, run 1 once, run 0's recomputation once;
    # backward: run 0 once
    assert count(0, 400 * US) == (4.0, 1.0)
    # a slice that ends inside run 0's second layer: its first layer
    # whole, of the second the projection and `intra` (median of 2, 1, 1)
    assert count(0, 55 * US) == (1.0, 0.0)
    assert count(300 * US, 400 * US) == (0.0, 0.0)


def test_an_instruction_inside_a_loop_of_its_own_moves_no_count():
    """An instruction that runs eight times a pass (the compiler's own
    loop) beside two that run once: the median holds."""
    ops = OPS + [(text("fusion.3"), (210 + i) * US, (211 + i) * US)
                 for i in range(8)]
    lines = [("XLA Ops", ops)]
    assert _ssm.scan_passes(lines, INSTRUCTIONS, 0, 400 * US, ("XLA Ops",)) == (
        4.0, 1.0
    )


@pytest.mark.parametrize("name,path,want", [
    ("fusion.1", FWD + "mamba2/run0/scan/intra/mul", ("ssm", "ssm_scan")),
    ("fusion.1", FWD + "mamba2/run1/gate_norm/mul", ("ssm",)),
    ("attention.2", REMAT + "attention/pallas_call", ("attention",)),
    ("fusion.1", FWD + "moe/shared/dot_general", ("moe",)),
    ("fusion.1", FWD + "moe/cond/branch_0_fun/experts/mul", ("moe", "experts")),
    ("ragged-dot-none.3", "ragged-dot-none", ("experts", "moe")),
    ("fusion.1", FWD + "gdn/scan/intra/mul", ()),  # another cell's scan
    ("fusion.1", FWD + "mlp/dot_general", ()),
    ("fusion.1", None, ()),
])
def test_an_instruction_counts_under_its_scopes_or_by_its_kernel_s_name(
    name, path, want
):
    with _hybrid._in_place_of(
        _moe, SHARES=_ssm.SHARES, shares_of=_shortconv.shares_of
    ):
        assert _moe.shares_of(name, path) == want


def run_directory(tmp_path, monkeypatch, instructions, spans=()):
    """A run directory whose trace is the hand-made plane."""
    for module in (_ssm, _shortconv, _hybrid, _moe, _timeline):
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


def test_the_walk_counts_passes_and_leaves_the_borrowed_tables(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    own = (dict(_moe.SHARES), _moe.shares_of, _moe.plane_seconds)
    found = _ssm.trace_seconds(run, reader)
    assert found["busy"] == pytest.approx(BUSY_US * 1e-6)
    assert found["kind"] == "TPU v5 lite"
    assert (found["forward"], found["backward"]) == (4.0, 1.0)
    assert found["kernels"] == 1.0  # the one grouped matmul
    share = lambda name: _ssm.share(run, reader, name)  # noqa: E731
    assert share("ssm") == pytest.approx(100 * SSM_US / BUSY_US)
    assert share("ssm_scan") == pytest.approx(100 * SCAN_US / BUSY_US)
    assert share("attention") == pytest.approx(100 * 30 / BUSY_US)
    assert share("moe") == pytest.approx(100 * 40 / BUSY_US)
    assert (dict(_moe.SHARES), _moe.shares_of, _moe.plane_seconds) == own
    assert _moe._cache == {}
    # the routed cell's reader after it, same process: its own table
    assert _moe.share(run, reader, "route") == pytest.approx(100 * 10 / BUSY_US)


def test_the_scan_s_roofline_credits_the_chunked_form_and_cannot_pass_100(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    tokens = SIZES["seq_len"]
    one = FLOPS.ssm_scan_flops(tokens, SIZES)
    # a token: the scores once a group, the rest a head
    assert one == 2 * tokens * (8 * 128 * 128 / 2 * 128 + 64 * (
        128 * 128 / 2 * 64 + 2 * 128 * 64 * 128 + 64 * 128
    )) / 128
    assert FLOPS.ssm_scan_macs(SIZES) == 1_380_352
    moved = FLOPS.ssm_scan_bytes(tokens, SIZES)
    assert moved == tokens * (2 * (2 * 64 * 64 + 2 * 8 * 128) + 4 * 64)
    roof = min(197e12, 819e9 * one / moved)  # the memory roof: 133 FLOP/B
    assert roof == pytest.approx(819e9 * one / moved) and 125 < one / moved < 140
    got = _ssm.scan_roofline(run, reader)
    # four forward passes and one backward (2 x) under mamba2/scan
    assert got == pytest.approx(100 * 6 * one / (SCAN_US * 1e-6) / roof)
    found = {"seconds": {"ssm_scan": one / roof}, "forward": 1.0, "backward": 0.0}
    assert _ssm.scan_roofline_pct(found, tokens, SIZES, FLOPS, 197e12, 819e9) == (
        pytest.approx(100.0)
    )
    found = {"seconds": {"ssm_scan": 0.0}, "forward": 0.0, "backward": 0.0}
    assert _ssm.scan_roofline_pct(found, tokens, SIZES, FLOPS, 197e12, 819e9) is None


SPANS = [
    (990.0, {"expert_tokens": [[9.0, 1.0]]}),  # before the window
    (1010.0, {"expert_tokens": [[3.0, 1.0], [2.0, 2.0]]}),  # 1.5, 1
    (1020.0, {"expert_tokens": [[4.0, 0.0], [0.0, 0.0]]}),  # 2, none
    (1030.0, {"ssm_dt_mean": 0.02}),
]


def test_the_experts_roofline_counts_two_matrix_experts_on_the_rows_that_came(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS, spans=SPANS)
    rows = (4 + 4 + 4 + 0) / 4  # the mean over spans and layers
    one = FLOPS.expert_matmul_flops(rows, SIZES)
    assert one == 2 * rows * 2688 * 1856
    moved = FLOPS.expert_matmul_bytes(rows, SIZES)
    assert moved == 2 * (rows * 2688 + rows * 1856 + 8 * 2688 * 1856)
    roof = min(197e12, 819e9 * one / moved)
    with mock.patch.object(
        _timeline, "find_run_dir",
        lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
    ):
        got = _ssm.experts_roofline(run, reader)
    # one grouped matmul in the 20 us under moe/experts
    assert got == pytest.approx(100 * one / 20e-6 / roof)


def test_the_load_ratio_is_the_fullest_held_expert_over_the_mean(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS, spans=SPANS)
    module = load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "nano_expert_load_max_over_mean.py",
    ))
    with mock.patch.object(
        _timeline, "find_run_dir",
        lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
    ):
        assert module.read(run) == pytest.approx((1.5 + 1.0 + 2.0) / 3)


def test_the_configuration_s_flops_by_hand():
    assert FLOPS.mamba2_macs(SIZES) == (
        2688 * 10304 + 4 * 6144 + 4096 * 2688
    ) == 38_731_776
    assert FLOPS.attention_macs(SIZES) == (
        2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
    )
    # 6 of 128 chosen, 8 held: 0.375 routed experts a token, two
    # matrices an expert
    assert FLOPS.expert_block_macs(SIZES) == (
        2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856
    )
    at = lambda length: harness_flops.flops_per_sample(  # noqa: E731
        {**SIZES, "seq_len": length}, CONFIG
    )
    pairs = 4096 * 4097 // 2
    assert FLOPS.score_macs(SIZES) == 2 * 32 * 128 * pairs
    assert at(4096) == 6 * (4096 * (
        3 * (38_731_776 + 1_380_352) + 23_396_352 + 3 * 24_041_472
        + 2688 * 16384
    ) + 2 * 32 * 128 * pairs)
    assert at(4096) == pytest.approx(6.7997e12, rel=1e-4)
    # ISSUE 56's count at the length it asked for: 14.4 TFLOP a sequence
    assert at(8192) == pytest.approx(14.42e12, rel=1e-3)
    assert SIZES["seq_len"] == SIZES["data"]["seq_len"] == 4096


def test_the_manifest_s_new_entries_are_found_by_name():
    manifest = manifest_lib.load(ROOT)
    assert manifest_lib.lint(manifest, ROOT) == []
    config = {c["name"]: c for c in manifest["configs"]}[SIZES["name"]]
    assert config["source"] == SIZES["source"]
    assert config["reduced"] == SIZES["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
    ]
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        SIZES["name"], "window16-serial-1w", 1
    )
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "goodput"
    for name in ("ssm_scan_roofline_pct", "relu2_experts_roofline_pct"):
        assert (metrics[name]["unit"], metrics[name]["layer"]) == ("%", "kernels")
    reported = manifest_lib.cell_metrics(manifest, CELL, "per_layer")
    assert set(READERS) <= set(reported)
    # every metric without a `workloads` list is this cell's too
    assert all(
        name in reported for name, m in metrics.items() if "workloads" not in m
    )
    assert set(manifest_lib.cell_metrics(manifest, CELL, "end_to_end")) == {
        "goodput", "setup_s",
    }


def test_the_configuration_states_its_source_cuts_and_assumptions():
    row = SIZES
    assert row["hybrid_override_pattern"] == (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    )
    assert len(row["hybrid_override_pattern"]) == row["published"]["num_hidden_layers"]
    assert row["held_layers"] == [0, 7] and row["held_experts"] == [0, 8]
    assert (row["num_hidden_layers"], row["n_routed_experts"], row["vocab_size"]) == (
        7, 8, 16384
    )
    assert row["published"]["n_routed_experts"] == 128
    assert row["published"]["vocab_size"] == 8 * row["vocab_size"]
    # no width is cut
    assert (row["hidden_size"], row["mamba_num_heads"], row["mamba_head_dim"],
            row["ssm_state_size"], row["n_groups"], row["conv_kernel"],
            row["chunk_size"], row["num_attention_heads"],
            row["num_key_value_heads"], row["head_dim"],
            row["moe_intermediate_size"],
            row["moe_shared_expert_intermediate_size"],
            row["num_experts_per_tok"], row["routed_scaling_factor"]) == (
        2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 1856, 3712, 6, 2.5
    )
    said = " ".join(row["assumed"])
    for word in ("unclamped", "gate THEN grouped norm", "turns nothing",
                 "ZERO", "no balance term", "initial values",
                 "rescale_prenorm_residual"):
        assert word in said, word
    assert row["parameters"] == 528_093_120
    rehearsal = row["minibatch_rehearsal"]
    assert rehearsal["at_1x8192"]["with_base_flat"] > 14.3e9  # the rule fell
    assert rehearsal["chosen"] == "1 sequence of 4096"


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
     "while.5": FWD + "gdn/scan/state/while",
     "fusion.8": FWD + "moe/route/sort"},  # another model's scopes
], ids=["no-map", "other-scopes"])
def test_a_run_without_the_scopes_reads_nothing_and_does_not_raise(
    tmp_path, monkeypatch, instructions
):
    """The parent commit these files are laid over has no `mamba2`
    scope for any cell: None, no error."""
    run, reader = run_directory(
        tmp_path, monkeypatch, instructions,
        spans=[(1010.0, {"held_share": 0.2})],
    )
    for name in _ssm.SHARES:
        assert _ssm.share(run, reader, name) is None
    assert _ssm.scan_roofline(run, reader) is None
    with mock.patch.object(
        _timeline, "find_run_dir",
        lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
    ):
        assert _ssm.experts_roofline(run, reader) is None
    for name in TRACE_READERS:
        module = load_module(
            os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        )
        with mock.patch.object(
            _timeline, "find_run_dir",
            lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
        ):
            assert module.read(run) is None
