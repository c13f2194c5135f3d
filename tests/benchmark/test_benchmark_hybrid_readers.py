"""The six readers of the `kimi-linear-48b-a3b` cell (`kda_pct`,
`kda_scan_pct`, `kda_scan_roofline_pct`, `hybrid_mla_pct`,
`hybrid_moe_pct`, `hybrid_expert_load_max_over_mean`) on hand-made
planes whose answer is known: leaf operations joined to their scope on
the HLO instruction's name, the passes over the chunks counted from the
`while`s under `kda/scan/state` (a recomputed forward pass is a forward
pass), and `flops.py`'s counts checked by hand."""

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
from benchmark.layer_metrics import _hybrid, _moe, _timeline  # noqa: E402

US = 1000  # ns
FWD = "jit(window)/while/body/closed_call/jvp()/while/body/closed_call/"
BACK = "jit(window)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/"
INSTRUCTIONS = {
    "while.1": "jit(window)/while",
    "fusion.2": FWD + "kda/conv/dot_general",
    "fusion.3": FWD + "kda/scan/intra/exp",
    "while.4": FWD + "kda/scan/state/while",
    "fusion.5": FWD + "kda/scan/state/while/body/closed_call/dot_general",
    "fusion.6": FWD + "kda/out/mul",
    "fusion.7": FWD + "mla/dot_general",
    "fusion.8": FWD + "moe/route/gather",
    "ragged-dot-none.1": "ragged-dot-none",  # the scope is lost
    "while.9": BACK + "rematted_computation/kda/scan/state/while",
    "fusion.10": BACK + "rematted_computation/kda/scan/state/while/body/closed_call/dot_general",
    "while.11": BACK + "kda/scan/state/while",
    "fusion.12": BACK + "kda/scan/state/while/body/closed_call/dot_general",
    "fusion.13": "jit(window)/while/body/closed_call/optimizer/add",
}


def text(name, kind="fusion"):
    return f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} %p), kind=kLoop"


# one step inside while.1 [0, 300): conv 20, intra 30, the forward pass
# over the chunks 40 (its body 36), out 10, mla 15, route 10, a grouped
# matmul 20, the recomputed pass 40 (body 38), the backward pass 60
# (body 50), the optimizer 45; 10 of while.1 its own
OPS = [
    (text("while.1", "while"), 0, 300 * US),
    (text("fusion.2"), 0, 20 * US),
    (text("fusion.3"), 20 * US, 50 * US),
    (text("while.4", "while"), 50 * US, 90 * US),
    (text("fusion.5"), 52 * US, 88 * US),
    (text("fusion.6"), 90 * US, 100 * US),
    (text("fusion.7"), 100 * US, 115 * US),
    (text("fusion.8"), 115 * US, 125 * US),
    (text("ragged-dot-none.1", "custom-call"), 125 * US, 145 * US),
    (text("while.9", "while"), 145 * US, 185 * US),
    (text("fusion.10"), 146 * US, 184 * US),
    (text("while.11", "while"), 185 * US, 245 * US),
    (text("fusion.12"), 190 * US, 240 * US),
    (text("fusion.13"), 245 * US, 290 * US),
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 300 * US)]), ("XLA Ops", OPS)]
CONFIG = os.path.join(ROOT, "benchmark", "configs", "kimi-linear-48b-a3b")
FLOPS = load_module(os.path.join(CONFIG, "flops.py"))
SIZES = load_module(os.path.join(CONFIG, "zoo.py")).SIZES


def walk(lo, hi):
    """`_moe.py`'s walk with this cell's table, and the passes."""
    with mock.patch.object(_moe, "SHARES", _hybrid.SHARES):
        seconds, busy, _kernels = _moe.plane_seconds(
            LINES, INSTRUCTIONS, lo, hi, ("XLA Ops",)
        )
    return (seconds, busy) + _hybrid.passes(
        LINES, INSTRUCTIONS, lo, hi, ("XLA Ops",)
    )


def test_shares_of_busy_time_and_the_passes_over_the_chunks():
    seconds, busy, forward, backward = walk(0, 400 * US)
    assert busy == pytest.approx(300e-6)
    # leaves: conv 20, intra 30, the three bodies 36 + 38 + 50, out 10
    assert seconds["kda"] == pytest.approx(184e-6)
    assert seconds["kda_scan"] == pytest.approx(154e-6)
    assert seconds["mla"] == pytest.approx(15e-6)
    assert seconds["experts"] == pytest.approx(30e-6)  # the kernel by its name
    # the recomputed pass ran forward: two forward, one backward
    assert (forward, backward) == (pytest.approx(2.0), pytest.approx(1.0))


def test_the_slice_clips_shares_and_passes_alike():
    seconds, busy, forward, backward = walk(70 * US, 215 * US)
    assert busy == pytest.approx(145e-6)
    assert seconds["kda_scan"] == pytest.approx((18 + 38 + 25) * 1e-6)
    assert forward == pytest.approx(0.5 + 1.0)  # half of the first
    assert backward == pytest.approx(0.5)


def test_the_walk_is_moe_py_s_own_with_this_cell_s_table(tmp_path, monkeypatch):
    """`trace_seconds` end to end on a run directory whose trace is the
    hand-made plane: `_moe.py`'s loop over maps, probes and planes with
    `_hybrid.SHARES` in place, the passes counted beside it, and
    `_moe.py` left with its own table and nothing cached."""
    for module in (_hybrid, _moe, _timeline):
        monkeypatch.setattr(module, "_cache", {})
    run_dir = tmp_path / ".bench_runs" / "cell-s1-t1"
    for sub in ("probe", "logs", "tb"):
        (run_dir / sub).mkdir(parents=True)
    (run_dir / "probe" / "trace.latch").write_text("1000.25")
    (run_dir / "logs" / "worker-0.hlo_scopes.json").write_text(json.dumps(
        {"program": "jit_window", "instructions": INSTRUCTIONS}
    ))
    (run_dir / "probe" / "77.json").write_text(json.dumps({
        "worker_id": 0, "kind": "TPU v5 lite",
        "trace": {"state": "written", "dir": str(tmp_path / "trace")},
    }))
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(
        trace_reduce, "load", lambda path: [("/device:TPU:0", LINES)]
    )
    monkeypatch.setattr(
        _timeline, "_slice_and_origin", lambda planes, info: ((0, 400 * US), 0)
    )
    reader = str(tmp_path / "benchmark" / "layer_metrics" / "x.py")
    (tmp_path / "benchmark" / "configs").mkdir(parents=True)
    os.symlink(CONFIG, tmp_path / "benchmark" / "configs" / SIZES["name"])
    run = {"platform": "tpu", "trace": {"busy_s": 1.0},
           "window": {"wall0": 1000.3, "wall1": 1045.3},
           "sizes": dict(SIZES)}
    own = dict(_moe.SHARES)
    found = _hybrid.trace_seconds(run, reader)
    assert found["busy"] == pytest.approx(300e-6)
    assert found["seconds"]["kda_scan"] == pytest.approx(154e-6)
    assert (found["forward"], found["backward"]) == (2.0, 1.0)
    assert found["kind"] == "TPU v5 lite"
    assert _hybrid.share(run, reader, "kda") == pytest.approx(100 * 184 / 300)
    assert _hybrid.share(run, reader, "experts") == pytest.approx(10.0)
    assert 0 < _hybrid.scan_roofline(run, reader)
    assert _moe.SHARES == own and _moe._cache == {}
    # the routed cell's reader after it, same process: its own table
    assert _moe.share(run, reader, "mla") == pytest.approx(5.0)


@pytest.mark.parametrize("name,path,want", [
    ("fusion.1", FWD + "kda/scan/intra/exp", ("kda", "kda_scan")),
    ("fusion.1", BACK + "kda/gates/logistic", ("kda",)),
    ("fusion.1", BACK + "rematted_computation/mla/dot_general", ("mla",)),
    ("fusion.1", FWD + "moe/shared/dot_general", ("experts",)),
    ("ragged-dot-none.3", "ragged-dot-none", ("experts",)),
    ("fusion.1", FWD + "mlp/dot_general", ()),  # the dense layer's
    ("fusion.1", FWD + "scan/while", ()),  # `kda` is a whole segment
    ("fusion.1", None, ()),
])
def test_an_instruction_counts_under_its_scopes_or_by_its_kernel_s_name(
    name, path, want
):
    with mock.patch.object(_moe, "SHARES", _hybrid.SHARES):
        assert _moe.shares_of(name, path) == want


def test_the_roofline_divides_the_credited_passes_by_the_scope_s_time():
    tokens = 2 * 2048
    one = FLOPS.kda_scan_flops(tokens, SIZES)
    assert one == 2 * tokens * 2228224  # 69,632 a head a token, 32 heads
    moved = FLOPS.kda_scan_bytes(tokens, SIZES)
    assert moved == tokens * 32 * (8 * 128 + 4 * 128 + 4)
    assert 85 < one / moved < 95  # memory-bound on the v5e (240 FLOP/B)
    found = {"seconds": {"kda_scan": 0.5}, "forward": 20.0, "backward": 10.0}
    roof = 819e9 * one / moved
    assert roof < 197e12
    got = _hybrid.roofline_pct(found, tokens, SIZES, FLOPS, 197e12, 819e9)
    assert got == pytest.approx(100 * 40 * one / 0.5 / roof)
    assert 1 < got < 3
    # a chip with bandwidth to spare is held to its peak
    assert _hybrid.roofline_pct(found, tokens, SIZES, FLOPS, 1e12, 819e9) == (
        pytest.approx(100 * 40 * one / 0.5 / 1e12)
    )
    idle = {"seconds": {"kda_scan": 0.0}, "forward": 0.0, "backward": 0.0}
    assert _hybrid.roofline_pct(idle, tokens, SIZES, FLOPS, 1, 1) is None


def test_the_configuration_s_flops_by_hand():
    assert FLOPS.kda_mixer_macs(SIZES) == (
        3 * 2304 * 4096 + 3 * 4 * 4096 + 2 * (2304 * 128 + 128 * 4096)
        + 2304 * 32 + 4096 * 2304
    ) == 39510016
    # a head's chunk: two triangles, the system, the state three times,
    # the triangle against the solved rows
    assert FLOPS.kda_scan_macs(SIZES) == 32 * (
        524288 + 524288 + 3145728 + 262144
    ) / 64 == 2228224
    assert FLOPS.mla_macs(SIZES) == (
        2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304
    ) == 29114368
    assert FLOPS.mla_score_macs(SIZES) == 32 * 320 * 2049 / 2 == 10490880
    expert_layer = 2304 * 256 + 7077888 + 8 * 8 / 256 * 7077888
    assert expert_layer == 9437184
    macs = (
        2304 * 20480
        + 39510016 + 2228224 + 3 * 2304 * 9216
        + 3 * (39510016 + 2228224 + expert_layer)
        + 29114368 + 10490880 + expert_layer
    )
    assert macs == 355193856
    assert FLOPS.flops_per_sample(SIZES) == 6 * macs * 2048
    assert harness_flops.flops_per_sample(SIZES, CONFIG) == pytest.approx(
        4.3646e12, rel=1e-4
    )


READERS = ("kda_pct", "kda_scan_pct", "kda_scan_roofline_pct",
           "hybrid_mla_pct", "hybrid_moe_pct")


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


def test_a_program_without_map_or_spans_reads_nothing_and_does_not_raise(
    tmp_path, monkeypatch
):
    """The parent commit these files are laid over writes no map with
    these scopes and no `expert_tokens` for this cell: None, no error."""
    monkeypatch.setattr(_hybrid, "_cache", {})
    monkeypatch.setattr(_moe, "_cache", {})
    monkeypatch.setattr(_timeline, "_cache", {})
    run_dir = tmp_path / ".bench_runs" / "cell-s1-t1"
    (run_dir / "probe").mkdir(parents=True)
    (run_dir / "logs").mkdir()
    (run_dir / "tb").mkdir()
    (run_dir / "probe" / "trace.latch").write_text("1000.25")
    reader = str(tmp_path / "benchmark" / "layer_metrics" / "x.py")
    run = {"platform": "tpu", "trace": {"busy_s": 1.0},
           "window": {"wall0": 1000.3, "wall1": 1045.3},
           "sizes": dict(SIZES),
           "mix": {"master_flags": {"local_updates": 16}}}
    assert _hybrid.share(run, reader, "kda") is None
    assert _hybrid.scan_roofline(run, reader) is None
    load = load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "hybrid_expert_load_max_over_mean.py",
    ))
    assert _moe.expert_tokens(run, reader) is None
    assert load.read is not None


# ------------------------------------------------ the configuration's files

CELL = "kimi-linear-48b-a3b.window16-serial-1w"


def test_the_configuration_states_its_source_cuts_and_sizes():
    import json

    from benchmark.harness import manifest as manifest_lib

    with open(os.path.join(CONFIG, "config.json")) as f:
        sizes = json.load(f)
    for key in ("source", "assumed", "reduced", "published", "deployment",
                "parameters_how", "minibatch_rehearsal", "layer_types"):
        assert sizes[key], key
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert sizes["published"]["num_hidden_layers"] == 27
    assert sizes["published"]["num_experts"] == 256 == 32 * sizes["num_experts"]
    assert sizes["published"]["vocab_size"] == 163840 == 8 * sizes["vocab_size"]
    assert sizes["held_experts"] == [0, 8] and "32 chips" in sizes["deployment"]
    assert sizes["layer_types"] == ["kda", "kda", "kda", "mla", "kda"]
    # the published widths, unchanged
    linear = sizes["linear_attn_config"]
    assert (sizes["hidden_size"], linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"], sizes["num_attention_heads"],
            sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
            sizes["v_head_dim"], sizes["kv_lora_rank"],
            sizes["intermediate_size"], sizes["moe_intermediate_size"],
            sizes["num_experts_per_token"], sizes["num_shared_experts"],
            sizes["routed_scaling_factor"], sizes["first_k_dense_replace"]) == (
        2304, 32, 128, 4, 32, 128, 64, 128, 512, 9216, 1024, 8, 1, 2.446, 1)
    assert sizes["mla_use_nope"] is True and sizes["q_lora_rank"] is None
    assert len(linear["kda_layers"]) == 20 and len(linear["full_attn_layers"]) == 7
    kda = (3 * 2304 * 4096 + 3 * 4 * 4096 + 32 + 2 * (2304 * 128 + 128 * 4096)
           + 4096 + 2304 * 32 + 128 + 4096 * 2304)
    mla = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    assert (kda, mla) == (39_514_272, 29_114_880)
    rest = 4608 + 590_080 + 9 * 7_077_888  # norms, router + bias, shared + 8
    assert (kda + rest, mla + rest) == (103_809_952, 93_410_560)
    assert sizes["parameters"] == (
        kda + 4608 + 3 * 2304 * 9216 + 3 * (kda + rest) + mla + rest
        + 2 * 20480 * 2304 + 2304
    ) == 602_434_432
    # the first frame over 2^31 bytes, under the 4 GiB a frame may have
    assert 2**31 < sizes["parameters"] * 4 == 2_409_737_728 < 2**32
    rehearsal = sizes["minibatch_rehearsal"]
    assert rehearsal["bytes_at_1"] <= rehearsal["bytes_at_2"] <= rehearsal["bytes_at_4"]
    assert sizes["records_per_task"] == 16 * sizes["minibatch_per_chip"] == 32
    with open(os.path.join(CONFIG, "zoo.py")) as f:
        assert "probe.start_if_worker()" in f.read()
    with open(os.path.join(CONFIG, "reference.py")) as f:
        assert "elasticdl_tpu" not in f.read().replace("`elasticdl_tpu`", "")
    committed = manifest_lib.load(ROOT)
    resolved = manifest_lib.resolve(committed, CELL, ROOT)
    assert resolved["cell"]["chips"] == 1
    assert resolved["mix"]["master_flags"] == {
        "local_updates": 16, "grads_to_wait": 1, "overlap_sync": "off"
    }
    assert resolved["config"]["reduced"] == sizes["reduced"]
    assert resolved["config"]["source"] == sizes["source"]
    reported = manifest_lib.cell_metrics(committed, CELL, "per_layer")
    for name in READERS + ("hybrid_expert_load_max_over_mean",):
        assert reported[name]["workloads"] == [CELL]
        assert reported[name]["moves"] == "goodput"
        assert os.path.isfile(manifest_lib.reader_file(name, ROOT))
    assert reported["kda_scan_roofline_pct"]["better"] == "higher"
    assert "mfu_pct" in reported  # the whole step's share, every cell's


def test_compare_py_holds_the_worker_s_own_step_and_the_scan_to_the_reference(
    tmp_path, monkeypatch
):
    """The script's plumbing at tiny sizes on the CPU (its band is not
    judged there): the float32 program inside `TIGHT`, the recurrence
    alone telling a rounded decay, a rounded state and a dropped state
    from the program's own."""
    import json

    compare = load_module(os.path.join(CONFIG, "compare.py"))
    monkeypatch.setattr(compare, "ROOT", str(tmp_path))
    assert compare.main(["--seed", "5", "--small"]) == 0
    with open(tmp_path / "chiprun_out" / "kimi_compare.jsonl") as f:
        verdict = json.loads(f.readline())
    assert verdict["float32_beyond_tight"] == {}
    found = verdict["measures"]
    assert found["float32"]["grad_rel_l2"] < 1e-4 < found["timed"]["grad_rel_l2"]
    own = found["timed"]["scan_rel"]
    assert own == found["float32"]["scan_rel"] == found["rotated"]["scan_rel"] < 1e-5
    for control in ("bf16_decay", "bf16_state", "dropped_state"):
        assert found[control]["scan_rel"] > compare.BAND["scan_rel"] > 10 * own
    assert found["bf16_router"]["router_flips"] > compare.BAND["router_flips"]
    assert found["timed"]["router_flips"] == 0
    assert set(compare.BAND) <= set(compare.TIGHT)
