"""The five readers of the `deepseek-v2-lite` cell (`moe_experts_pct`,
`moe_route_pct`, `mla_attention_pct`, `experts_roofline_pct`,
`expert_load_max_over_mean`) on hand-made planes and spans whose answer
is known: leaf operations joined to their scope on the HLO
instruction's name, the compiler's grouped-matmul kernels (which lose
their scope) counted under `moe/experts` by their name, the roofline
from the kernels really run and the rows really routed."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark.harness.manifest import load_module  # noqa: E402
from benchmark.layer_metrics import _moe  # noqa: E402

US = 1000  # ns
FWD = "jit(window)/while/body/closed_call/jvp()/while/body/closed_call/"
BACK = "jit(window)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/"
INSTRUCTIONS = {
    "while.1": "jit(window)/while",
    "fusion.2": FWD + "mla/dot_general",
    "fusion.3": FWD + "moe/route/gather",
    "fusion.4": FWD + "moe/experts/jit(silu)/mul",
    "ragged-dot-none.1": "ragged-dot-none",  # the scope is lost
    "ragged-dot-metadata.1": "ragged-dot-metadata",
    "fusion.5": FWD + "moe/shared/dot_general",
    "fusion.6": BACK + "rematted_computation/moe/route/jit(_where)/select_n",
    "fusion.7": FWD + "mlp/dot_general",  # the dense layer's: no share
    "fusion.8": "jit(window)/while/body/add",  # the optimizer
}


def text(name, kind="fusion"):
    return f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} %p), kind=kLoop"


# one step inside while.1 [0, 200): mla 30, route 20 + 10, the metadata
# call 2, two grouped matmuls of 20 each, silu-mul 8, shared 15, mlp 25,
# the optimizer 40; 10 of while.1 its own
OPS = [
    (text("while.1"), 0, 200 * US),
    (text("fusion.2"), 0, 30 * US),
    (text("fusion.3"), 30 * US, 50 * US),
    (text("ragged-dot-metadata.1", "custom-call"), 50 * US, 52 * US),
    (text("ragged-dot-none.1", "custom-call"), 52 * US, 72 * US),
    (text("fusion.4"), 72 * US, 80 * US),
    (text("ragged-dot-none.1", "custom-call"), 80 * US, 100 * US),
    (text("fusion.5"), 100 * US, 115 * US),
    (text("fusion.6"), 115 * US, 125 * US),
    (text("fusion.7"), 125 * US, 150 * US),
    (text("fusion.8"), 150 * US, 190 * US),
]
LINES = [("XLA Modules", [("jit_window(1)", 0, 200 * US)]), ("XLA Ops", OPS)]
SIZES = {"hidden_size": 2048, "moe_intermediate_size": 1408, "n_routed_experts": 8}
FLOPS = load_module(os.path.join(
    ROOT, "benchmark", "configs", "deepseek-v2-lite", "flops.py"
))


def test_shares_of_busy_time_and_the_kernels_counted_by_name():
    seconds, busy, kernels = _moe.plane_seconds(
        LINES, INSTRUCTIONS, 0, 300 * US, ("XLA Ops",)
    )
    assert busy == pytest.approx(200e-6)
    assert seconds["mla"] == pytest.approx(30e-6)
    assert seconds["route"] == pytest.approx(30e-6)  # forward and recomputed
    assert seconds["experts"] == pytest.approx(50e-6)  # 2 + 20 + 8 + 20
    assert kernels == pytest.approx(2.0)


def test_the_slice_clips_shares_busy_time_and_kernels_alike():
    seconds, busy, kernels = _moe.plane_seconds(
        LINES, INSTRUCTIONS, 62 * US, 120 * US, ("XLA Ops",)
    )
    assert busy == pytest.approx(58e-6)
    assert seconds["experts"] == pytest.approx(38e-6)  # 10 + 8 + 20
    assert seconds["route"] == pytest.approx(5e-6)
    assert seconds["mla"] == 0.0
    assert kernels == pytest.approx(1.5)  # half of the first, the second


@pytest.mark.parametrize("name,path,want", [
    ("ragged-dot-none.7", "ragged-dot-none", ("experts",)),
    ("ragged-dot-metadata", None, ("experts",)),
    ("fusion.1", FWD + "moe/experts/mul", ("experts",)),
    ("fusion.1", FWD + "moe/route/top_k", ("route",)),
    ("fusion.1", BACK + "mla/jit(_rope)/mul", ("mla",)),
    ("fusion.1", FWD + "moe/shared/dot_general", ()),
    ("fusion.1", FWD + "formula/experts/dot", ()),  # `moe` is a whole segment
    ("fusion.1", None, ()),
])
def test_an_instruction_counts_under_its_scope_or_by_its_kernel_s_name(name, path, want):
    assert _moe.shares_of(name, path) == want


def test_load_is_the_fullest_expert_over_the_mean():
    even = [[[10] * 8] * 4]
    assert _moe.load_max_over_mean(even) == pytest.approx(1.0)
    one = [[[80, 0, 0, 0, 0, 0, 0, 0]] * 4]
    assert _moe.load_max_over_mean(one) == pytest.approx(8.0)
    mixed = [[[20, 10, 10, 0], [0, 0, 0, 0]], [[10, 10, 10, 10]]]
    # (2.0 + 1.0) / 2: the layer that sent nothing here is left out
    assert _moe.load_max_over_mean(mixed) == pytest.approx(1.5)
    assert _moe.load_max_over_mean([[[0, 0]]]) is None


def test_the_roofline_divides_the_kernels_work_by_the_scope_s_time():
    found = {"seconds": {"experts": 1.0}, "kernels": 500.0}
    loads = [[[768] * 8] * 4]  # uniform routing at 4 x 2048 tokens
    one = 2 * 6144 * 2048 * 1408
    assert FLOPS.expert_matmul_flops(6144, SIZES) == one
    intensity = one / FLOPS.expert_matmul_bytes(6144, SIZES)
    assert 350 < intensity < 450  # compute-bound on the v5e (240 FLOP/B)
    got = _moe.roofline_pct(found, loads, SIZES, FLOPS, 197e12, 819e9)
    assert got == pytest.approx(100 * 500 * one / 197e12)
    assert 8 < got < 10
    # a memory-bound matmul (few rows) is held to the bandwidth's roof
    few = [[[1] * 8] * 4]
    thin = FLOPS.expert_matmul_flops(8, SIZES)
    roof = 819e9 * thin / FLOPS.expert_matmul_bytes(8, SIZES)
    assert roof < 197e12
    assert _moe.roofline_pct(found, few, SIZES, FLOPS, 197e12, 819e9) == (
        pytest.approx(100 * 500 * thin / roof)
    )
    assert _moe.roofline_pct(
        {"seconds": {"experts": 0.0}, "kernels": 0.0}, loads, SIZES, FLOPS, 1, 1
    ) is None


READERS = ("moe_experts_pct", "moe_route_pct", "mla_attention_pct",
           "experts_roofline_pct")


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


def _run_dir(tmp_path, spans=None, scope_map=None):
    run_dir = tmp_path / ".bench_runs" / "cell-s1-t1"
    (run_dir / "probe").mkdir(parents=True)
    (run_dir / "logs").mkdir()
    (run_dir / "tb").mkdir()
    (run_dir / "probe" / "trace.latch").write_text("1000.25")
    if spans is not None:
        with open(run_dir / "logs" / "worker-0.spans.jsonl", "w") as f:
            for span in spans:
                f.write(json.dumps(span) + "\n")
        (run_dir / "tb" / "master.spans.jsonl").write_text("")
    if scope_map is not None:
        with open(run_dir / "logs" / "worker-0.hlo_scopes.json", "w") as f:
            json.dump({"program": "jit_window", "instructions": scope_map}, f)
    reader = tmp_path / "benchmark" / "layer_metrics" / "x.py"
    run = {"platform": "tpu", "trace": {"busy_s": 1.0},
           "window": {"wall0": 1000.3, "wall1": 1045.3}, "sizes": {"name": "c"},
           "mix": {"master_flags": {"local_updates": 16}}}
    return run, str(reader)


def test_a_program_without_map_or_spans_reads_nothing_and_does_not_raise(
    tmp_path, monkeypatch
):
    """The parent commit these files are laid over writes no map and no
    `worker.window_stats` with `expert_tokens`: None, not an error."""
    from benchmark.layer_metrics import _timeline

    monkeypatch.setattr(_moe, "_cache", {})
    monkeypatch.setattr(_timeline, "_cache", {})
    run, reader = _run_dir(tmp_path)
    assert _moe.share(run, reader, "experts") is None
    assert _moe.experts_roofline(run, reader) is None
    assert _moe.expert_tokens(run, reader) is None  # no span file at all


def test_the_load_is_read_from_the_window_s_spans(tmp_path, monkeypatch):
    from benchmark.layer_metrics import _timeline

    monkeypatch.setattr(_timeline, "_cache", {})

    def span(ts, **args):
        return {"name": "worker.window_stats", "ts": ts, "dur": 0.0, "pid": 7,
                "tid": 1, "args": {"thread": "sync", "steps": 16, **args}}

    spans = [
        span(990.0, expert_tokens=[[80, 0, 0, 0]]),  # set-up: not the window's
        span(1010.0, expert_tokens=[[10, 10, 10, 10], [40, 0, 0, 0]]),
        span(1020.0, expert_tokens=[[20, 10, 10, 0], [10, 10, 10, 10]]),
        span(1030.0, exit_q=[0.5, 0.5]),  # another model's stats
        {"name": "compute", "ts": 1011.0, "dur": 1.0, "pid": 7, "tid": 1,
         "args": {"thread": "MainThread"}},
    ]
    run, reader = _run_dir(tmp_path, spans=spans)
    loads = _moe.expert_tokens(run, reader)
    assert loads == [[[10, 10, 10, 10], [40, 0, 0, 0]],
                     [[20, 10, 10, 0], [10, 10, 10, 10]]]
    assert _moe.load_max_over_mean(loads) == pytest.approx((1 + 4 + 2 + 1) / 4)
    # the reader itself only forwards to these two
    module = load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "expert_load_max_over_mean.py"
    ))
    monkeypatch.setattr(module, "__file__", reader)
    assert module.read(run) == pytest.approx(2.0)
