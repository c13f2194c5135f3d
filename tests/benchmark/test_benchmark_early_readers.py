"""The seven readers of the `smallthinker-21b-a3b` cell
(`early_route_pct`, `band4k_attention_pct`, `nope_attention_pct`,
`early_moe_pct`, `band4k_roofline_pct`, `reglu_experts_roofline_pct`,
`early_expert_load_max_over_mean`) on hand-made planes whose answer is
known: leaf operations joined to their scope on the HLO instruction's
name, the `router` scope's operations counted with `moe/route`'s, the
attention kernels of each kind by their `op_name`, the grouped matmuls
by their instruction's name, the banded kernels' calls counted forward
and backward and held to `flops.py`'s roofline; nothing, and no error,
on a run without the scopes. And the committed manifest's new entries,
found BY NAME, and what the configuration's file states."""

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
    _early,
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
    "fusion.1": FWD + "router/dot_general",
    "fusion.2": FWD + "attention/swa/dot_general",
    "fusion.3": FWD + "attention/swa/rope/mul",
    "swa.4": FWD + "attention/swa/pallas_call",
    "fusion.6": FWD + "attention/global/dot_general",
    "global.7": FWD + "attention/global/pallas_call",
    "fusion.8": FWD + "moe/route/sort",
    "ragged-dot-none.1": "ragged-dot-none",  # the scope is lost
    "fusion.9": FWD + "moe/cond/branch_0_fun/experts/mul",
    "swa.10": BACK + "rematted_computation/attention/swa/pallas_call",
    "swa.11": BACK + "attention/swa/pallas_call",
    "swa.12": BACK + "attention/swa/pallas_call",
    "global.13": BACK + "attention/global/pallas_call",
    "fusion.16": BACK + "router/dot_general",  # the router's backward product
    "fusion.14": FWD + "head/dot_general",
    "fusion.15": "jit(window)/while/body/closed_call/optimizer/add",
}


def text(name, kind="fusion"):
    return f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} %p), kind=kLoop"


# one step inside while.1 [0, 300): the early router's product 4; a
# sliding layer's projection 20, rotation 10, forward kernel 10; the
# full layer's projection 15 and kernel 30; the sort 10, a grouped
# matmul 10, the ReLU-and-multiply 5; the recomputed banded forward 10,
# dq 20 and dk+dv 30; the full layer's backward kernel 40; the router's
# backward product 6; the head 35, the optimizer 35; 10 of while.1 its own
OPS = [
    (text("while.1", "while"), 0, 300 * US),
    (text("fusion.1"), 0, 4 * US),
    (text("fusion.2"), 4 * US, 24 * US),
    (text("fusion.3"), 24 * US, 34 * US),
    (text("swa.4", "custom-call"), 34 * US, 44 * US),
    (text("fusion.6"), 44 * US, 59 * US),
    (text("global.7", "custom-call"), 59 * US, 89 * US),
    (text("fusion.8"), 89 * US, 99 * US),
    (text("ragged-dot-none.1", "custom-call"), 99 * US, 109 * US),
    (text("fusion.9"), 109 * US, 114 * US),
    (text("swa.10", "custom-call"), 114 * US, 124 * US),
    (text("swa.11", "custom-call"), 124 * US, 144 * US),
    (text("swa.12", "custom-call"), 144 * US, 174 * US),
    (text("global.13", "custom-call"), 174 * US, 214 * US),
    (text("fusion.16"), 214 * US, 220 * US),
    (text("fusion.14"), 220 * US, 255 * US),
    (text("fusion.15"), 255 * US, 290 * US),
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 300 * US)]), ("XLA Ops", OPS)]
NAME = "smallthinker-21b-a3b"
CONFIG = os.path.join(ROOT, "benchmark", "configs", NAME)
FLOPS = load_module(os.path.join(CONFIG, "flops.py"))
with open(os.path.join(CONFIG, "config.json")) as _f:
    SIZES = json.load(_f)
CELL = NAME + ".window16-serial-1w"
TRACE_READERS = (
    "early_route_pct", "band4k_attention_pct", "nope_attention_pct",
    "early_moe_pct", "band4k_roofline_pct", "reglu_experts_roofline_pct",
)
READERS = TRACE_READERS + ("early_expert_load_max_over_mean",)
BAND = 58_722_304  # the pairs a window of 4096 leaves of 16,384 tokens


def walk(lo, hi):
    """`_moe.py`'s walk with this cell's table and its reading of a
    grouped matmul and of the `router` scope."""
    with _hybrid._in_place_of(
        _moe, SHARES=_early.SHARES, shares_of=_early.shares_of
    ):
        return _moe.plane_seconds(LINES, INSTRUCTIONS, lo, hi, ("XLA Ops",))


def test_shares_of_busy_time_by_scope_and_by_kernel_name():
    seconds, busy, grouped = walk(0, 400 * US)
    assert busy == pytest.approx(300e-6)
    assert grouped == 1.0
    # the router's two products under `router`, the sort under `moe/route`
    assert seconds["router"] == pytest.approx((4 + 6) * 1e-6)
    assert seconds["route"] == pytest.approx(10 * 1e-6)
    assert seconds["swa"] == pytest.approx((20 + 10 + 10 + 10 + 20 + 30) * 1e-6)
    assert seconds["global"] == pytest.approx((15 + 30 + 40) * 1e-6)
    # the sort, the grouped matmul by its name, the ReLU-and-multiply;
    # the router's own product is not under `moe`
    assert seconds["moe"] == pytest.approx((10 + 10 + 5) * 1e-6)
    assert seconds["experts"] == pytest.approx((10 + 5) * 1e-6)


@pytest.mark.parametrize("name,path,want", [
    ("fusion.1", FWD + "router/dot_general", ("router",)),
    ("fusion.1", BACK + "rematted_computation/router/dot_general", ("router",)),
    ("fusion.1", FWD + "moe/route/gather", ("route", "moe")),
    ("fusion.1", FWD + "attention/swa/rope/mul", ("swa",)),
    ("swa.2", BACK + "rematted_computation/attention/swa/pallas_call", ("swa",)),
    ("global.3", BACK + "attention/global/pallas_call", ("global",)),
    ("fusion.1", FWD + "moe/cond/branch_1_fun/experts/mul", ("moe", "experts")),
    ("ragged-dot-none.3", "ragged-dot-none", ("moe", "experts")),
    ("ragged-dot-metadata.3", "ragged-dot-metadata", ("moe", "experts")),
    ("fusion.1", FWD + "attention/dot_general", ()),  # another cell's attention
    ("fusion.1", FWD + "head/dot_general", ()),
    ("fusion.1", None, ()),
])
def test_an_instruction_counts_under_its_scopes_or_by_its_kernel_s_name(
    name, path, want
):
    with _hybrid._in_place_of(
        _moe, SHARES=_early.SHARES, shares_of=_early.shares_of
    ):
        assert _moe.shares_of(name, path) == want


def run_directory(tmp_path, monkeypatch, instructions, spans=()):
    """A run directory whose trace is the hand-made plane."""
    for module in (_early, _window, _hybrid, _moe, _timeline):
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
    os.symlink(CONFIG, tmp_path / "benchmark" / "configs" / NAME)
    reader = str(tmp_path / "benchmark" / "layer_metrics" / "x.py")
    run = {"platform": "tpu", "trace": {"busy_s": 1.0},
           "window": {"wall0": 1000.3, "wall1": 1045.3},
           "sizes": dict(SIZES),
           "mix": {"master_flags": {"local_updates": 16}}}
    return run, reader


def read(name, run, tmp_path):
    module = load_module(
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    )
    with mock.patch.object(
        _timeline, "find_run_dir",
        lambda run, reader_file: str(tmp_path / ".bench_runs" / "cell-s1-t1"),
    ):
        return module.read(run)


def test_the_walk_is_moe_py_s_own_with_this_cell_s_table(tmp_path, monkeypatch):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    own, own_shares_of, own_walk = (
        dict(_moe.SHARES), _moe.shares_of, _moe.plane_seconds
    )
    found = _early.trace_seconds(run, reader)
    assert found["busy"] == pytest.approx(300e-6)
    assert found["kind"] == "TPU v5 lite"
    assert (found["forward"], found["backward"]) == (2.0, 2.0)
    assert found["kernel_seconds"] == pytest.approx(70e-6)
    assert found["kernels"] == 1.0
    assert _early.share(run, reader, "router") == pytest.approx(100 * 10 / 300)
    assert _early.share(run, reader, "router", "route") == pytest.approx(
        100 * 20 / 300
    )
    assert _early.share(run, reader, "swa") == pytest.approx(100 * 100 / 300)
    assert _early.share(run, reader, "global") == pytest.approx(100 * 85 / 300)
    assert _early.share(run, reader, "moe") == pytest.approx(100 * 25 / 300)
    assert _moe.SHARES == own and _moe.shares_of is own_shares_of
    assert _moe.plane_seconds is own_walk and _moe._cache == {}
    # the routed cell's reader after it, same process: its own table,
    # which knows no `router` scope
    assert _moe.share(run, reader, "route") == pytest.approx(100 * 10 / 300)


def test_each_reader_s_file_reads_its_share(tmp_path, monkeypatch):
    run, _reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS, spans=[
        (1010.0, {"expert_tokens": [[300.0, 100.0], [200.0, 200.0]]}),
    ])
    assert read("early_route_pct", run, tmp_path) == pytest.approx(100 * 20 / 300)
    assert read("band4k_attention_pct", run, tmp_path) == pytest.approx(100 * 100 / 300)
    assert read("nope_attention_pct", run, tmp_path) == pytest.approx(100 * 85 / 300)
    assert read("early_moe_pct", run, tmp_path) == pytest.approx(100 * 25 / 300)
    assert read("early_expert_load_max_over_mean", run, tmp_path) == (
        pytest.approx((1.5 + 1.0) / 2)
    )
    # one grouped matmul of the mean layer's 400 rows in the 15 us under
    # moe/experts
    one = FLOPS.expert_matmul_flops(400, SIZES)
    assert one == 2 * 400 * 2560 * 768
    roof = min(197e12, 819e9 * one / FLOPS.expert_matmul_bytes(400, SIZES))
    assert read("reglu_experts_roofline_pct", run, tmp_path) == pytest.approx(
        100 * one / 15e-6 / roof
    )


def test_the_roofline_credits_the_band_s_pairs_and_cannot_pass_100(
    tmp_path, monkeypatch
):
    run, reader = run_directory(tmp_path, monkeypatch, INSTRUCTIONS)
    forward = FLOPS.swa_call_flops(SIZES, FLOPS.FORWARD_PRODUCTS)
    backward = FLOPS.swa_call_flops(SIZES, FLOPS.BACKWARD_PRODUCTS / 2)
    assert forward == 4 * 28 * 128 * BAND == pytest.approx(841.84e9, rel=1e-4)
    assert backward == 7 * 28 * 128 * BAND
    tensor = 2 * 16384 * 28 * 128  # one array of [tokens, heads, 128] in bf16
    assert FLOPS.swa_call_bytes(SIZES, 4) == 4 * tensor
    # a band eight times as wide as Laguna's: far on the compute side
    assert forward / (4 * tensor) > 1500
    got = _early.band_roofline(run, reader)
    # two forward and two backward calls in 70 us
    assert got == pytest.approx(
        100 * (2 * forward + 2 * backward) / 197e12 / 70e-6
    )
    assert read("band4k_roofline_pct", run, tmp_path) == pytest.approx(got)
    # a call that took the least the chip could take reads 100, not more
    found = {"kernel_seconds": forward / 197e12, "forward": 1.0, "backward": 0.0}
    assert _window.roofline_pct(found, SIZES, FLOPS, 197e12, 819e9) == (
        pytest.approx(100.0)
    )


def test_the_configuration_s_flops_by_hand():
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert FLOPS.attention_macs(SIZES) == attention == 20_971_520
    assert FLOPS.visible_pairs(16384) == 134_225_920
    assert FLOPS.visible_pairs(16384, 4096) == BAND
    assert BAND / 134_225_920 == pytest.approx(0.4375, abs=1e-3)
    assert FLOPS.visible_pairs(8192, 4096) / FLOPS.visible_pairs(8192) == (
        pytest.approx(0.75, abs=1e-3)
    )
    assert FLOPS.score_macs(SIZES) == 2 * 28 * 128 * 134_225_920
    assert FLOPS.score_macs(SIZES, 4096) == 2 * 28 * 128 * BAND
    s = 16384
    forward = 2 * (
        4 * s * attention + FLOPS.score_macs(SIZES)
        + 3 * FLOPS.score_macs(SIZES, 4096)
        + 4 * s * (2560 * 64 + 0.75 * 3 * 2560 * 768)
        + s * 2560 * 18992
    )
    # ISSUE 62's count: 9.37 T forward a step, attention's kernels 4.45 T
    assert forward == pytest.approx(9.39e12, rel=2e-3)
    kernels = 2 * (FLOPS.score_macs(SIZES) + 3 * FLOPS.score_macs(SIZES, 4096))
    assert kernels / forward == pytest.approx(0.47, abs=0.01)
    assert harness_flops.flops_per_sample(SIZES, CONFIG) == pytest.approx(
        3 * forward
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
     "custom-call.6": FWD + "attention/pallas_call",
     "fusion.8": FWD + "moe/route/sort"},  # another model's scopes
], ids=["no-map", "other-scopes"])
def test_a_run_without_the_scopes_reads_nothing_and_does_not_raise(
    tmp_path, monkeypatch, instructions
):
    """The parent commit these files are laid over has no `router`
    scope and cannot build this model: None, no error."""
    run, reader = run_directory(tmp_path, monkeypatch, instructions)
    for name in ("router", "route", "swa", "global", "moe"):
        assert _early.share(run, reader, name) is None
    assert _early.band_roofline(run, reader) is None
    assert _early.experts_roofline(run, reader) is None
    for name in READERS:
        assert read(name, run, tmp_path) is None


# ------------------------------------------------ the manifest, by name


def test_the_committed_manifest_holds_the_new_entries_by_name_and_lints_clean():
    committed = manifest_lib.load(ROOT)
    assert manifest_lib.lint(committed, ROOT) == []
    config = {c["name"]: c for c in committed["configs"]}[NAME]
    assert config["source"] == SIZES["source"]
    assert config["file"] == f"benchmark/configs/{NAME}/config.json"
    assert config["reduced"] == SIZES["reduced"]
    cell = {w["name"]: w for w in committed["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "window16-serial-1w", 1
    )
    metrics = {m["name"]: m for m in committed["per_layer"]}
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL], name
        assert metrics[name]["moves"] == "goodput", name
        assert os.path.isfile(manifest_lib.reader_file(name, ROOT)), name
    for name in ("band4k_roofline_pct", "reglu_experts_roofline_pct"):
        assert (metrics[name]["layer"], metrics[name]["unit"]) == ("kernels", "%")
    assert metrics["early_expert_load_max_over_mean"]["source"] == "program_span"
    # the cell reports the new seven beside every metric that lists no cells
    wanted = manifest_lib.cell_metrics(committed, CELL, "per_layer")
    assert set(READERS) <= set(wanted)
    assert {"mfu_pct", "window_exposed_pct", "window_resident_gb"} <= set(wanted)
    assert not any(
        other.startswith(("swa_", "nano_", "sambay_")) for other in wanted
    )
    assert set(manifest_lib.cell_metrics(committed, CELL, "end_to_end")) == {
        "goodput", "setup_s",
    }
    resolved = manifest_lib.resolve(committed, CELL, ROOT)
    assert resolved["cell"]["chips"] == 1
    assert resolved["mix"]["master_flags"] == {
        "local_updates": 16, "grads_to_wait": 1, "overlap_sync": "off",
    }


def test_the_configuration_states_its_source_cuts_and_sizes():
    sizes = SIZES
    for key in ("source", "assumed", "reduced", "published", "deployment",
                "parameters_how", "minibatch_rehearsal", "loss_check"):
        assert sizes[key], key
    assert sizes["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
    ]
    published = sizes["published"]
    assert published["num_hidden_layers"] == 52 == len(sizes["rope_layout"])
    assert len(sizes["sliding_window_layout"]) == 52
    assert published["moe_num_primary_experts"] == 64 == (
        8 * sizes["moe_num_primary_experts"]
    )
    assert published["vocab_size"] == 151936 == 8 * sizes["vocab_size"]
    assert sizes["held_experts"] == [0, 8] and "8 chips" in sizes["deployment"]
    first, count = sizes["held_layers"]
    assert (first, count) == (0, 4) == (0, sizes["num_hidden_layers"])
    # one whole period: full and unturned, then three windowed and turned
    assert sizes["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert sizes["rope_layout"] == sizes["sliding_window_layout"]
    # every width as published
    assert (sizes["hidden_size"], sizes["num_attention_heads"],
            sizes["num_key_value_heads"], sizes["head_dim"],
            sizes["sliding_window_size"], sizes["rope_theta"],
            sizes["moe_ffn_hidden_size"],
            sizes["moe_num_active_primary_experts"]) == (
        2560, 28, 4, 128, 4096, 1500000, 768, 6
    )
    # every number of the catalog's row under its key, but the three cuts
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # the guide's, where this checkout has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert sizes["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in sizes["reduced"]:
                assert sizes[key] == value, key
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    layer = attention + 2 * 2560 + 2560 * 64 + 8 * 3 * 2560 * 768
    assert layer == 68_326_400
    assert sizes["parameters"] == 4 * layer + 2 * 18992 * 2560 + 2560 == (
        370_547_200
    )
    assert any("un-normed" in line and "ln1" in line for line in sizes["assumed"])
    rehearsal = sizes["minibatch_rehearsal"]
    chosen = rehearsal[rehearsal["chosen_key"]]
    assert chosen["with_base_flat"] == (
        chosen["program_alone"] + 4 * sizes["parameters"]
    ) < 14.5e9
    assert sizes["records_per_task"] == 16 * sizes["minibatch_per_chip"] == 16
    assert sizes["seq_len"] == sizes["data"]["seq_len"] == (
        sizes["max_position_embeddings"]
    )
    assert sizes["data"]["alphabet"] <= sizes["vocab_size"]
    with open(os.path.join(CONFIG, "zoo.py")) as f:
        assert "probe.start_if_worker()" in f.read()
    with open(os.path.join(CONFIG, "reference.py")) as f:
        source = f.read()
    assert "elasticdl_tpu" not in source and "import benchmark" not in source
    assert "pallas" not in source and "lax.scan" not in source


def test_the_zoo_module_builds_the_block_the_file_states():
    import jax.numpy as jnp

    zoo = load_module(os.path.join(CONFIG, "zoo.py"))
    cfg = zoo.custom_model().cfg
    assert cfg.runs == (("mha", True, 1), ("swa", True, 3))
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (
        2560, 28, 4, 128
    )
    assert cfg.early_router and cfg.mlp == "reglu" and cfg.rope_mixers == ("swa",)
    swa, full = cfg.attention_shape("swa"), cfg.attention_shape("mha")
    assert (swa.heads, swa.window, swa.rope_base, swa.turns) == (
        28, 4096, 1500000.0, True
    )
    assert (full.heads, full.window, full.turns) == (28, None, False)
    assert (cfg.n_experts, cfg.held, cfg.d_expert, cfg.moe_top_k) == (
        64, (0, 8), 768, 6
    )
    assert (cfg.n_shared_experts, cfg.moe_score, cfg.moe_renormalize,
            cfg.aux_weight, cfg.remat, cfg.dtype) == (
        0, "softmax", True, 0.0, True, jnp.bfloat16
    )
    ref = load_module(os.path.join(CONFIG, "reference.py"))
    sizes = ref.sizes_of(SIZES)
    assert sizes["kinds"] == ("full", "sliding", "sliding", "sliding")
    assert sizes["full"] == {"window": None, "turns": False}
    assert sizes["sliding"] == {"window": 4096, "turns": True}
    assert (sizes["top_k"], sizes["held"], sizes["router_reads"]) == (
        6, (0, 8), "input"
    )
