"""The six readers of the `phi-4-mini-flash-reasoning` cell
(`selscan_pct`, `sambay_mamba_pct`, `sambay_gmu_pct`,
`sambay_attention_pct`, `sambay_cross_pct`, `selscan_roofline_pct`) on
hand-made planes whose answer is known: leaf operations joined to their
scope on the HLO instruction's name, the Mamba-1 layer's passes counted
from its first projection (whatever implements the scan: a kernel that
is one instruction a pass, or a loop whose body runs once a chunk),
forward and backward, and held to `flops.py`'s roofline; nothing, and no
error, on a run without the scopes; the manifest's new entries found by
name; `flops.py`'s count by hand."""

import json
import os
import sys

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
    _sambay,
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
    "fusion.2": FWD + "mamba1/run1/in_proj/dot_general",
    "fusion.3": FWD + "mamba1/run1/step/dot_general",
    "selscan.4": FWD + "mamba1/run1/scan/pallas_call",  # the kernel
    "fusion.5": FWD + "mamba1/run1/scan/add",  # D x
    "fusion.6": FWD + "mamba1/run1/gate/mul",
    "fusion.7": FWD + "gmu/in_proj/dot_general",
    "fusion.8": FWD + "gmu/gate/mul",
    "fusion.9": FWD + "attention/swa/dot_general",
    "attention.10": FWD + "attention/global/pallas_call",
    "fusion.11": FWD + "attention/cross/dot_general",
    "attention.12": FWD + "attention/cross/pallas_call",
    "fusion.13": FWD + "attention/cross/diff/mul",
    "fusion.14": FWD + "mlp/dot_general",
    # the layer recomputed, then transposed: two products of the
    # projection's, one backward kernel
    "fusion.15": REMAT + "mamba1/run1/in_proj/dot_general",
    "selscan.16": REMAT + "mamba1/run1/scan/pallas_call",
    "fusion.17": BACK + "mamba1/run1/in_proj/dot_general",
    "fusion.18": BACK + "mamba1/run1/in_proj/transpose",
    "selscan.19": BACK + "mamba1/run1/scan/pallas_call",
    "fusion.20": "jit(window)/while/body/closed_call/optimizer/add",
}


def text(name, kind="fusion"):
    return f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} %p), kind=kLoop"


# one step inside while.1 [0, 300)
OPS = [
    (text("while.1", "while"), 0, 300 * US),
    (text("fusion.2"), 0, 10 * US), (text("fusion.3"), 10 * US, 15 * US),
    (text("selscan.4", "custom-call"), 15 * US, 35 * US),
    (text("fusion.5"), 35 * US, 40 * US), (text("fusion.6"), 40 * US, 45 * US),
    (text("fusion.7"), 45 * US, 55 * US), (text("fusion.8"), 55 * US, 60 * US),
    (text("fusion.9"), 60 * US, 70 * US),
    (text("attention.10", "custom-call"), 70 * US, 90 * US),
    (text("fusion.11"), 90 * US, 100 * US),
    (text("attention.12", "custom-call"), 100 * US, 120 * US),
    (text("fusion.13"), 120 * US, 125 * US),
    (text("fusion.14"), 125 * US, 165 * US),
    (text("fusion.15"), 165 * US, 175 * US),
    (text("selscan.16", "custom-call"), 175 * US, 195 * US),
    (text("fusion.17"), 195 * US, 205 * US), (text("fusion.18"), 205 * US, 215 * US),
    (text("selscan.19", "custom-call"), 215 * US, 265 * US),
    (text("fusion.20"), 265 * US, 270 * US),
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 300 * US)]), ("XLA Ops", OPS)]
SCAN_US = 20 + 5 + 20 + 50
MAMBA_US = SCAN_US + 10 + 5 + 5 + 10 + 10 + 10
BUSY_US = 300  # `while.1` covers the window
CONFIG = os.path.join(ROOT, "benchmark", "configs", "phi-4-mini-flash-reasoning")
FLOPS = load_module(os.path.join(CONFIG, "flops.py"))
with open(os.path.join(CONFIG, "config.json")) as _f:
    SIZES = json.load(_f)
CELL = "phi-4-mini-flash-reasoning.window16-serial-1w"
READERS = ("selscan_pct", "sambay_mamba_pct", "sambay_gmu_pct",
           "sambay_attention_pct", "sambay_cross_pct", "selscan_roofline_pct")


def run_directory(tmp_path, monkeypatch, instructions, lines=LINES):
    """A run directory whose trace is the hand-made plane."""
    for module in (_sambay, _ssm, _shortconv, _hybrid, _moe, _timeline):
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
    (run_dir / "logs" / "worker-0.spans.jsonl").write_text("")
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(
        trace_reduce, "load", lambda path: [("/device:TPU:0", lines)]
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


def test_the_walk_reads_the_shares_and_leaves_the_borrowed_tables(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    own = (dict(_moe.SHARES), _moe.plane_seconds, _ssm.SCAN)
    found = _sambay.trace_seconds(run, reader)
    assert found["busy"] == pytest.approx(BUSY_US * 1e-6)
    assert found["kind"] == "TPU v5 lite"
    # the first pass and the recomputation; the transposed copy once
    assert (found["forward"], found["backward"]) == (2.0, 1.0)
    share = lambda name: _sambay.share(run, reader, name)  # noqa: E731
    assert share("selscan") == pytest.approx(100 * SCAN_US / BUSY_US)
    assert share("mamba") == pytest.approx(100 * MAMBA_US / BUSY_US)
    assert share("gmu") == pytest.approx(100 * 15 / BUSY_US)
    assert share("attention") == pytest.approx(100 * 65 / BUSY_US)
    assert share("cross") == pytest.approx(100 * 35 / BUSY_US)
    assert (dict(_moe.SHARES), _moe.plane_seconds, _ssm.SCAN) == own
    assert _moe._cache == {}
    for name, want in (("selscan_pct", SCAN_US), ("sambay_mamba_pct", MAMBA_US),
                       ("sambay_gmu_pct", 15), ("sambay_attention_pct", 65),
                       ("sambay_cross_pct", 35)):
        module = load_module(
            os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        )
        monkeypatch.setattr(
            _timeline, "find_run_dir",
            lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
        )
        assert module.read(run) == pytest.approx(100 * want / BUSY_US), name


def test_the_passes_do_not_depend_on_what_implements_the_scan(
    tmp_path, monkeypatch
):
    """The plain-jax form in the kernel's place: a loop whose body's
    instructions run once a CHUNK, 64 times a pass. The passes are the
    layer's, counted from its projection: the same."""
    instructions = {
        **INSTRUCTIONS, "fusion.30": FWD + "mamba1/run1/scan/while/body/mul",
    }
    ops = [op for op in OPS if "selscan.4" not in op[0]] + [
        (text("fusion.30"), (15 + i * 0.25) * US, (15 + (i + 1) * 0.25) * US)
        for i in range(64)
    ]
    run, reader = run_directory(
        tmp_path, monkeypatch, instructions, [("XLA Ops", ops)]
    )
    found = _sambay.trace_seconds(run, reader)
    assert (found["forward"], found["backward"]) == (2.0, 1.0)
    assert found["seconds"]["selscan"] == pytest.approx((SCAN_US - 4) * 1e-6)


def test_the_roofline_credits_the_recurrence_as_written_and_cannot_pass_100(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    tokens = SIZES["seq_len"]
    one = FLOPS.selscan_flops(tokens, SIZES)
    # three multiply-accumulates a token, channel and state column
    assert FLOPS.selscan_macs(SIZES) == 3 * 5120 * 16
    assert one == 2 * 4096 * 3 * 5120 * 16 == 2_013_265_920
    moved = FLOPS.selscan_bytes(tokens, SIZES)
    assert moved == 4096 * (5120 * (2 + 4 + 4) + 2 * 16 * 2)
    roof = min(197e12, 819e9 * one / moved)  # the memory roof: 9.6 FLOP/B
    assert roof == pytest.approx(819e9 * one / moved) and 9.5 < one / moved < 9.7
    got = _sambay.scan_roofline(run, reader)
    # two forward passes and one backward (2 x) under mamba1/scan
    assert got == pytest.approx(100 * 4 * one / (SCAN_US * 1e-6) / roof)
    found = {"seconds": {"selscan": one / roof}, "forward": 1.0, "backward": 0.0}
    assert _sambay.scan_roofline_pct(
        found, tokens, SIZES, FLOPS, 197e12, 819e9
    ) == pytest.approx(100.0)
    found = {"seconds": {"selscan": 0.0}, "forward": 0.0, "backward": 0.0}
    assert _sambay.scan_roofline_pct(
        found, tokens, SIZES, FLOPS, 197e12, 819e9
    ) is None


def test_the_configuration_s_flops_by_hand():
    assert FLOPS.mlp_macs(SIZES) == 3 * 2560 * 10240
    assert FLOPS.attention_macs(SIZES) == 2 * 2560 * 2560 + 2 * 2560 * 1280
    assert FLOPS.attention_macs(SIZES, cross=True) == 2 * 2560 * 2560
    assert FLOPS.mamba_macs(SIZES) == (
        2560 * 10240 + 4 * 5120 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    ) == 41_144_320
    assert FLOPS.gmu_macs(SIZES) == 2 * 2560 * 5120
    full = 4096 * 4097 // 2
    band = 512 * 513 // 2 + (4096 - 512) * 512
    assert FLOPS.visible_pairs(4096) == full
    assert FLOPS.visible_pairs(4096, 512) == band
    assert FLOPS.visible_pairs(300, 512) == 300 * 301 // 2
    # a visible pair, map and pair of heads: scores of 64, values of 128
    assert FLOPS.score_macs(SIZES) == 2 * 20 * (64 + 128) * full
    assert FLOPS.score_macs(SIZES, 512) == 2 * 20 * 192 * band
    assert [FLOPS.kind_of(i, 32) for i in range(14, 20)] == [
        "mamba", "sliding", "mamba", "full", "gmu", "cross",
    ]
    want = 6 * (4096 * (
        2560 * 25008 + 5 * 3 * 2560 * 10240
        + 2 * 19_660_800 + 13_107_200  # two attention layers, the cross one
        + 41_144_320 + 3 * 5120 * 16 + 2 * 2560 * 5120
    ) + 2 * 20 * 192 * (2 * full + band))
    assert harness_flops.flops_per_sample(SIZES, CONFIG) == want
    # ISSUE 58: about 15 TFLOP a sequence
    assert want == pytest.approx(15.05e12, rel=1e-3)
    assert SIZES["seq_len"] == SIZES["data"]["seq_len"] == 4096


def test_the_manifest_s_new_entries_are_found_by_name():
    manifest = manifest_lib.load(ROOT)
    assert manifest_lib.lint(manifest, ROOT) == []
    config = {c["name"]: c for c in manifest["configs"]}[SIZES["name"]]
    assert config["source"] == SIZES["source"]
    assert config["reduced"] == SIZES["reduced"] == [
        "num_hidden_layers", "vocab_size",
    ]
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        SIZES["name"], "window16-serial-1w", 1
    )
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert (metrics[name]["moves"], metrics[name]["unit"]) == ("goodput", "%")
    assert metrics["selscan_roofline_pct"]["layer"] == "kernels"
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
    catalog = {  # the catalog row's config, key by key
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064,
    }
    differs = sorted(k for k, v in catalog.items() if row[k] != v)
    assert differs == sorted(row["reduced"])
    assert row["published"]["num_hidden_layers"] == 32
    assert row["published"]["vocab_size"] == 8 * row["vocab_size"] == 200064
    assert row["held_layers"] == [15, 5] and row["num_hidden_layers"] == 5
    assert row["assumed_sizes"]["mamba_expand"] * row["hidden_size"] == 5120
    said = " ".join(row["assumed"])
    for word in ("DIFFERENTIAL", "lambda_init", "log-uniform", "A_log",
                 "BEFORE the gate", "bias on W_qkv", "window of 512",
                 "optimizer", "recomputation"):
        assert word in said, word
    assert "deployment" in row and "eight chips" in row["deployment"]
    assert row["parameters"] == 577_199_232 == (
        2 * 98_322_304 + 119_895_040 + 104_867_840 + 91_766_144
        + 64_020_480 + 5_120
    )
    rehearsal = row["minibatch_rehearsal"]
    assert rehearsal["at_1x4096"]["with_base_flat"] <= 15.2e9  # the rule held
    assert rehearsal["chosen"] == "1 sequence of 4096"


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("run", [
    {"platform": "cpu", "trace": {"busy_s": 1.0}},
    {"platform": "tpu", "trace": None},
], ids=["off-the-tpu", "untraced"])
def test_off_the_tpu_or_untraced_the_readers_say_nothing(reader, run):
    module = load_module(
        os.path.join(ROOT, "benchmark", "layer_metrics", reader + ".py")
    )
    assert module.read(run) is None


@pytest.mark.parametrize("instructions", [
    None,  # a program that writes no map
    {"while.1": "jit(window)/while", "fusion.2": FWD + "attention/dot_general",
     "attention.10": FWD + "attention/pallas_call",
     "fusion.3": FWD + "mamba2/run0/scan/intra/dot_general",
     "fusion.14": FWD + "mlp/dot_general"},  # another model's scopes
], ids=["no-map", "other-scopes"])
def test_a_run_without_the_scopes_reads_nothing_and_does_not_raise(
    tmp_path, monkeypatch, instructions
):
    """A parent commit these files are laid over has no `mamba1` scope
    for any cell: None, no error."""
    run, reader = run_directory(tmp_path, monkeypatch, instructions)
    for name in _sambay.SHARES:
        assert _sambay.share(run, reader, name) is None
    assert _sambay.scan_roofline(run, reader) is None
    monkeypatch.setattr(
        _timeline, "find_run_dir",
        lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
    )
    for name in READERS:
        module = load_module(
            os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        )
        assert module.read(run) is None
