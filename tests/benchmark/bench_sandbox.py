"""A temporary copy of the benchmark with one tiny cell added — by new
files and new manifest entries only, which is what the discovery test
shows and what the CPU rehearsal runs."""

import json
import os
import shutil

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

TINY_ZOO = '''
import json, os, sys
_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
from benchmark.harness import probe
from elasticdl_tpu.models.transformer_lm_zoo import (  # noqa: F401
    TransformerLM, dataset_fn, eval_metrics_fn, loss, optimizer,
)
with open(os.path.join(_HERE, "config.json")) as _f:
    SIZES = json.load(_f)
probe.start_if_worker()


def custom_model():
    return TransformerLM(
        vocab=SIZES["vocab_size"], d_model=SIZES["hidden_size"],
        n_heads=SIZES["num_attention_heads"],
        d_ff=SIZES["intermediate_size"],
        n_layers=SIZES["num_hidden_layers"],
    )
'''

TINY_SIZES = {
    "name": "tiny-lm", "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 2, "intermediate_size": 64, "vocab_size": 64,
    "seq_len": 32, "minibatch_per_chip": 4, "records_per_task": 8,
    "data": {"kind": "tokens", "seq_len": 32, "alphabet": 64, "records": 64},
    "loss_check": {"last_over_first_at_most": 1.25},
    "flops": {"formula": "dense_transformer"},
}

TINY_READER = '''
"""Tasks completed in the window (a count; any platform)."""


def read(run):
    first, last = run["snaps"][0], run["snaps"][-1]
    return float(last["completed"] - first["completed"])
'''


def copy_benchmark(tmp_path):
    root = os.path.join(str(tmp_path), "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    return root


def add_tiny_cell(root):
    """-> the new cell's name. Adds configs/tiny-lm/, traffic/tiny.json,
    layer_metrics/tiny_tasks.py and their entries; edits no file the
    benchmark had except the manifest it appends to."""
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "configs", "tiny-lm"))
    with open(os.path.join(bench, "configs", "tiny-lm", "config.json"), "w") as f:
        json.dump(TINY_SIZES, f)
    with open(os.path.join(bench, "configs", "tiny-lm", "zoo.py"), "w") as f:
        f.write(TINY_ZOO)
    with open(os.path.join(bench, "traffic", "tiny.json"), "w") as f:
        json.dump({
            "workers": 1,
            "master_flags": {"local_updates": 2, "grads_to_wait": 1},
        }, f)
    with open(os.path.join(bench, "layer_metrics", "tiny_tasks.py"), "w") as f:
        f.write(TINY_READER)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-lm", "source": "https://example.org/tiny",
        "file": "benchmark/configs/tiny-lm/config.json", "reduced": [],
        "why": "a toy for the tests",
    })
    manifest["workloads"].append({
        "name": "tiny-lm.tiny", "config": "tiny-lm", "traffic": "tiny",
        "chips": 1, "why": "a toy for the tests",
    })
    manifest["per_layer"].append({
        "name": "tiny_tasks", "unit": "records", "better": "higher",
        "source": "program_counter", "layer": "task dispatch",
        "moves": "goodput", "workloads": ["tiny-lm.tiny"],
    })
    with open(path, "w") as f:
        json.dump(manifest, f)
    return "tiny-lm.tiny"
