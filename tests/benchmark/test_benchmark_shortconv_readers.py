"""The six readers of the `lfm2-24b-a2b` cell (`shortconv_pct`,
`shortconv_gate_pct`, `gqa_attention_pct`, `sparse_moe_pct`,
`sparse_experts_roofline_pct`, `sparse_expert_load_max_over_mean`) on
hand-made planes whose answer is known: leaf operations joined to their
scope on the HLO instruction's name, the attention kernels by their
`op_name`, the grouped matmuls by their instruction's name (under
`moe/experts` and under the whole of `moe`), and `flops.py`'s counts
checked by hand; nothing, and no error, on a run without the scopes."""

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
    _shortconv,
    _timeline,
)

US = 1000  # ns
FWD = "jit(window)/while/body/closed_call/jvp()/while/body/closed_call/"
BACK = "jit(window)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/"
INSTRUCTIONS = {
    "while.1": "jit(window)/while",
    "fusion.2": FWD + "conv/shortconv/in_proj/dot_general",
    "fusion.3": FWD + "conv/shortconv/gate_conv/mul",
    "fusion.4": FWD + "conv/shortconv/out_proj/dot_general",
    "fusion.5": FWD + "attention/dot_general",
    "custom-call.6": FWD + "attention/pallas_call",
    "fusion.7": FWD + "moe/route/sort",
    "ragged-dot-none.1": "ragged-dot-none",  # the scope is lost
    "fusion.8": FWD + "moe/experts/mul",
    "fusion.9": BACK + "rematted_computation/conv/shortconv/gate_conv/mul",
    "fusion.10": BACK + "conv/shortconv/gate_conv/reduce_max",
    "custom-call.11": BACK + "attention/pallas_call",
    "ragged-dot-none.2": "ragged-dot-none",
    "fusion.12": FWD + "mlp/dot_general",
    "fusion.13": "jit(window)/while/body/closed_call/optimizer/add",
}


def text(name, kind="fusion"):
    return f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} %p), kind=kLoop"


# one step inside while.1 [0, 300): in 20, gate 10, out 15, attention's
# projection 10 and kernel 15, the sort 25, a grouped matmul 20, the
# SiLU-and-multiply 5, the recomputed gate 10, the gate's backward 20,
# the backward kernel 30, a backward grouped matmul 40, the dense MLP
# 30, the optimizer 40; 10 of while.1 its own
OPS = [
    (text("while.1", "while"), 0, 300 * US),
    (text("fusion.2"), 0, 20 * US),
    (text("fusion.3"), 20 * US, 30 * US),
    (text("fusion.4"), 30 * US, 45 * US),
    (text("fusion.5"), 45 * US, 55 * US),
    (text("custom-call.6", "custom-call"), 55 * US, 70 * US),
    (text("fusion.7"), 70 * US, 95 * US),
    (text("ragged-dot-none.1", "custom-call"), 95 * US, 115 * US),
    (text("fusion.8"), 115 * US, 120 * US),
    (text("fusion.9"), 120 * US, 130 * US),
    (text("fusion.10"), 130 * US, 150 * US),
    (text("custom-call.11", "custom-call"), 150 * US, 180 * US),
    (text("ragged-dot-none.2", "custom-call"), 180 * US, 220 * US),
    (text("fusion.12"), 220 * US, 250 * US),
    (text("fusion.13"), 250 * US, 290 * US),
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 300 * US)]), ("XLA Ops", OPS)]
CONFIG = os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b")
FLOPS = load_module(os.path.join(CONFIG, "flops.py"))
SIZES = load_module(os.path.join(CONFIG, "zoo.py")).SIZES
CELL = "lfm2-24b-a2b.window16-serial-1w"
READERS = ("shortconv_pct", "shortconv_gate_pct", "gqa_attention_pct",
           "sparse_moe_pct", "sparse_experts_roofline_pct")


def walk(lo, hi):
    """`_moe.py`'s walk with this cell's table and its reading of a
    grouped matmul."""
    with _hybrid._in_place_of(
        _moe, SHARES=_shortconv.SHARES, shares_of=_shortconv.shares_of
    ):
        return _moe.plane_seconds(LINES, INSTRUCTIONS, lo, hi, ("XLA Ops",))


def test_shares_of_busy_time_by_scope_and_by_kernel_name():
    seconds, busy, kernels = walk(0, 400 * US)
    assert busy == pytest.approx(300e-6)
    assert seconds["shortconv"] == pytest.approx((20 + 10 + 15 + 10 + 20) * 1e-6)
    assert seconds["gate"] == pytest.approx((10 + 10 + 20) * 1e-6)
    assert seconds["attention"] == pytest.approx((10 + 15 + 30) * 1e-6)
    # the two grouped matmuls by their name, and the multiply between
    assert seconds["experts"] == pytest.approx((20 + 5 + 40) * 1e-6)
    assert seconds["moe"] == pytest.approx((25 + 20 + 5 + 40) * 1e-6)
    assert kernels == pytest.approx(2.0)


def test_the_slice_clips_the_shares_and_the_kernels_alike():
    seconds, busy, kernels = walk(25 * US, 200 * US)
    assert busy == pytest.approx(175e-6)
    assert seconds["gate"] == pytest.approx((5 + 10 + 20) * 1e-6)
    assert seconds["moe"] == pytest.approx((25 + 20 + 5 + 20) * 1e-6)
    assert kernels == pytest.approx(1.5)  # half of the second


@pytest.mark.parametrize("name,path,want", [
    ("fusion.1", FWD + "conv/shortconv/gate_conv/mul", ("shortconv", "gate")),
    ("fusion.1", BACK + "conv/shortconv/in_proj/dot_general", ("shortconv",)),
    ("custom-call.2", BACK + "rematted_computation/attention/pallas_call",
     ("attention",)),
    ("fusion.1", FWD + "moe/route/gather", ("moe",)),
    ("fusion.1", FWD + "moe/experts/mul", ("moe", "experts")),
    ("ragged-dot-none.3", "ragged-dot-none", ("experts", "moe")),
    ("ragged-dot-metadata.3", "ragged-dot-metadata", ("experts", "moe")),
    ("fusion.1", FWD + "mlp/dot_general", ()),  # the dense layer's
    ("fusion.1", FWD + "kda/conv/dot_general", ()),  # Kimi's convolution
    ("fusion.1", FWD + "gate_conv/mul", ()),  # `shortconv` is a whole segment
    ("fusion.1", None, ()),
])
def test_an_instruction_counts_under_its_scopes_or_by_its_kernel_s_name(
    name, path, want
):
    with _hybrid._in_place_of(
        _moe, SHARES=_shortconv.SHARES, shares_of=_shortconv.shares_of
    ):
        assert _moe.shares_of(name, path) == want


def run_directory(tmp_path, monkeypatch, instructions, spans=()):
    """A run directory whose trace is the hand-made plane."""
    for module in (_shortconv, _hybrid, _moe, _timeline):
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
    if spans:
        (run_dir / "tb" / "master.spans.jsonl").write_text("")
        with open(run_dir / "logs" / "worker-0.spans.jsonl", "w") as f:
            for ts, tokens in spans:
                f.write(json.dumps({
                    "name": "worker.window_stats", "cat": "phase", "ts": ts,
                    "dur": 0.0, "pid": 1, "tid": 1,
                    "args": {"expert_tokens": tokens, "steps": 16},
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
    and planes with `_shortconv.SHARES` in place, and `_moe.py` left
    with its own table, its own `shares_of` and nothing cached."""
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    own, own_shares_of = dict(_moe.SHARES), _moe.shares_of
    found = _shortconv.trace_seconds(run, reader)
    assert found["busy"] == pytest.approx(300e-6)
    assert found["kernels"] == pytest.approx(2.0)
    assert found["kind"] == "TPU v5 lite"
    assert _shortconv.share(run, reader, "shortconv") == pytest.approx(100 * 75 / 300)
    assert _shortconv.share(run, reader, "gate") == pytest.approx(100 * 40 / 300)
    assert _shortconv.share(run, reader, "attention") == pytest.approx(100 * 55 / 300)
    assert _shortconv.share(run, reader, "moe") == pytest.approx(30.0)
    assert _moe.SHARES == own and _moe.shares_of is own_shares_of
    assert _moe._cache == {}
    # the routed cell's reader after it, same process: its own table
    assert _moe.share(run, reader, "route") == pytest.approx(100 * 25 / 300)


def test_the_roofline_is_the_routed_cell_s_rule_with_this_cell_s_sizes(
    tmp_path, monkeypatch
):
    rows = [[400.0, 600.0, 500.0, 548.0, 512.0, 512.0, 512.0, 512.0]] * 4
    run, reader = run_directory(
        tmp_path, monkeypatch, INSTRUCTIONS, spans=[(1010.0, rows), (1020.0, rows)]
    )
    one = FLOPS.expert_matmul_flops(4096, SIZES)
    assert one == 2 * 4096 * 2048 * 1536
    moved = FLOPS.expert_matmul_bytes(4096, SIZES)
    assert moved == 2 * (4096 * 2048 + 4096 * 1536 + 8 * 2048 * 1536)
    assert 300 < one / moved < 340  # compute-bound on the v5e (240 FLOP/B)
    got = _shortconv.experts_roofline(run, reader)
    # two grouped matmuls of 4096 rows in 65 us under moe/experts
    assert got == pytest.approx(100 * 2 * one / 65e-6 / 197e12)
    assert _moe._cache == {}
    load = load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "sparse_expert_load_max_over_mean.py"
    ))
    assert load.read.__module__ != _moe.__name__
    assert _moe.load_max_over_mean(
        _moe.expert_tokens(run, reader)
    ) == pytest.approx(600 / 512)


def test_the_configuration_s_flops_by_hand():
    assert FLOPS.conv_mixer_macs(SIZES) == 2048 * 6144 + 2048 * 2048 == 16777216
    assert FLOPS.attention_macs(SIZES) == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10485760
    assert FLOPS.attention_score_macs(SIZES) == 32 * 128 * 2049 / 2 == 4196352
    expert_layer = 2048 * 64 + 4 * 8 / 64 * 9437184
    assert expert_layer == 4849664
    macs = (
        2048 * 8192
        + 16777216 + 3 * 2048 * 11776
        + 10485760 + 4196352 + expert_layer
        + 3 * (16777216 + expert_layer)
    )
    assert macs == 190318592
    assert FLOPS.flops_per_sample(SIZES) == 6 * macs * 2048
    assert harness_flops.flops_per_sample(SIZES, CONFIG) == pytest.approx(
        2.3386e12, rel=1e-4
    )
    assert 3 * 2048 * 11776 + 16777216 == 89128960  # the dense layer: 47 %
    assert 0.46 < 89128960 / macs < 0.48


@pytest.mark.parametrize("reader", READERS)
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
    {"while.1": "jit(window)/while", "fusion.2": FWD + "kda/conv/dot_general",
     "fusion.7": FWD + "moe/route/sort"},  # another model's scopes
], ids=["no-map", "other-scopes"])
def test_a_run_without_the_scopes_reads_nothing_and_does_not_raise(
    tmp_path, monkeypatch, instructions
):
    """The parent commit these files are laid over has no `shortconv`
    scope and writes no `expert_tokens` for this cell: None, no error."""
    run, reader = run_directory(tmp_path, monkeypatch, instructions)
    for name in ("shortconv", "gate", "attention", "moe"):
        assert _shortconv.share(run, reader, name) is None
    assert _shortconv.experts_roofline(run, reader) is None
    assert _moe.expert_tokens(run, reader) is None
    for name in READERS + ("sparse_expert_load_max_over_mean",):
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
                "parameters_how", "minibatch_rehearsal", "layer_types"):
        assert sizes[key], key
    assert sizes["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "num_dense_layers"
    ]
    published = sizes["published"]
    assert published["num_hidden_layers"] == 40 == len(sizes["layer_types"])
    assert published["num_experts"] == 64 == 8 * sizes["num_experts"]
    assert published["vocab_size"] == 65536 == 8 * sizes["vocab_size"]
    assert published["num_dense_layers"] == 2
    assert sizes["held_experts"] == [0, 8] and "8 chips" in sizes["deployment"]
    # the published pattern whole, and the layers held here: the second
    # dense layer and one whole period
    kinds = sizes["layer_types"]
    assert kinds.count("conv") == 30 and kinds.count("full_attention") == 10
    first, count = sizes["held_layers"]
    assert (first, count) == (1, 5) == (1, sizes["num_hidden_layers"])
    assert kinds[first:first + count] == [
        "conv", "full_attention", "conv", "conv", "conv"
    ]
    # every number of the catalog's row under its key, but the four cuts
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # the guide's, where this checkout has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
        assert sizes["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in sizes["reduced"]:
                assert sizes[key] == value, key
    assert (sizes["hidden_size"], sizes["num_attention_heads"],
            sizes["num_key_value_heads"], sizes["intermediate_size"],
            sizes["moe_intermediate_size"], sizes["num_experts_per_tok"],
            sizes["conv_L_cache"], sizes["routed_scaling_factor"]) == (
        2048, 32, 8, 11776, 1536, 4, 3, 1)
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    rest = 4096 + 2048 * 64 + 64 + 8 * 3 * 2048 * 1536
    assert (conv, attention) == (16_783_360, 10_485_888)
    assert (conv + rest, attention + rest) == (92_416_064, 86_118_592)
    assert sizes["parameters"] == (
        conv + 4096 + 3 * 2048 * 11776 + attention + rest + 3 * (conv + rest)
        + 8192 * 2048 + 2048
    ) == 469_285_248
    rehearsal = sizes["minibatch_rehearsal"]
    assert rehearsal["bytes_at_2"] <= rehearsal["bytes_at_4"] <= rehearsal["bytes_at_8"]
    assert rehearsal["bytes_at_4"] < 14.5e9
    assert sizes["records_per_task"] == 16 * sizes["minibatch_per_chip"] == 64
    with open(os.path.join(CONFIG, "zoo.py")) as f:
        assert "probe.start_if_worker()" in f.read()
    with open(os.path.join(CONFIG, "reference.py")) as f:
        source = f.read()
    assert "elasticdl_tpu" not in source and "import benchmark" not in source
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
    for name in READERS + ("sparse_expert_load_max_over_mean",):
        assert reported[name]["workloads"] == [CELL]
        assert reported[name]["moves"] == "goodput"
        assert reported[name]["layer"] == "worker step"
        assert os.path.isfile(manifest_lib.reader_file(name, ROOT))
    assert reported["sparse_experts_roofline_pct"]["better"] == "higher"
    assert "mfu_pct" in reported  # the whole step's share, every cell's
    # appended, nothing before them moved
    assert [m["name"] for m in committed["per_layer"][-6:]] == list(
        READERS + ("sparse_expert_load_max_over_mean",)
    )
    assert committed["workloads"][-1]["name"] == CELL
    assert committed["configs"][-1]["name"] == "lfm2-24b-a2b"


def test_compare_py_holds_the_worker_s_own_step_and_the_mixers_to_the_reference(
    tmp_path, monkeypatch
):
    """The script's plumbing at tiny sizes on the CPU (its band is not
    judged there): the float32 program inside `TIGHT`, each mixer alone
    telling its controls from the program's own."""
    compare = load_module(os.path.join(CONFIG, "compare.py"))
    monkeypatch.setattr(compare, "ROOT", str(tmp_path))
    assert compare.main(["--seed", "5", "--small"]) == 0
    with open(tmp_path / "chiprun_out" / "lfm2_compare.jsonl") as f:
        verdict = json.loads(f.readline())
    assert verdict["float32_beyond_tight"] == {}
    found = verdict["measures"]
    assert found["float32"]["grad_rel_l2"] < 1e-4 < found["timed"]["grad_rel_l2"]
    band = compare.BAND
    for mixer, controls in (
        ("conv_rel", ("activated_taps", "shifted_taps")),
        ("attn_rel", ("no_qk_norm", "interleaved_groups")),
    ):
        own = found["timed"][mixer]
        assert found["float32"][mixer] < 1e-5 < own < band[mixer]
        for control in controls:
            assert found[control][mixer] > 4 * band[mixer]
    assert found["activated_taps"]["attn_rel"] == found["timed"]["attn_rel"]
    assert found["bf16_router"]["router_flips"] > band["router_flips"]
    assert found["timed"]["router_flips"] == 0
    assert found["unnormalised"]["grad_rel_l2"] > band["grad_rel_l2"]
    assert set(compare.BAND) == set(compare.TIGHT)
    assert set(compare.SWAPS) | {"unnormalised"} == set(compare.CONTROLS)
