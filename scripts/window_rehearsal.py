"""A configuration's window program compiled for a described TPU v5e,
with no chip: what the v5e compiler says the worker's `jit_window`
(the scan of `--local_updates` steps of loss + gradient + optimizer,
the Pallas kernels in it) takes of the chip's memory, before a chip
minute is spent on a size that does not fit.

    python scripts/window_rehearsal.py --config smallthinker-21b-a3b \
        [--seq_len 8192] [--steps 16] [--set key=value ...]

Builds the program as the worker does (`Worker._build_local_window_fn`
on the zoo module's model, flat vectors or leaves by the worker's own
rule), lowers it on shapes alone for `v5e:2x2`'s first device and
prints one JSON line: `memory_analysis`'s arguments, outputs, aliased,
temporaries and code, their sum as `program_alone`, and
`with_base_flat`, that plus 4 B a parameter for the base the serial
chain holds beside the program. Nothing executes; `jax.default_backend`
is told "tpu" for the time of the trace, so that the dispatcher takes
the kernels and the window keeps its loop as on the chip. `--seq_len`
replaces the configuration's length, `--set` any keyword of its
`custom_model` (a Python literal).
"""

import argparse
import ast
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.harness.manifest import load_module  # noqa: E402
from elasticdl_tpu.api.model_spec import ModelSpec  # noqa: E402
from elasticdl_tpu.obs import hlo_scopes  # noqa: E402
from elasticdl_tpu.worker.worker import Worker, carries_leaves  # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seq_len", type=int)
    parser.add_argument("--steps", type=int, default=16)
    parser.add_argument("--set", action="append", default=[])
    args = parser.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    zoo = load_module(
        os.path.join(ROOT, "benchmark", "configs", args.config, "zoo.py")
    )
    overrides = {
        key: ast.literal_eval(value)
        for key, value in (item.split("=", 1) for item in args.set)
    }
    model = zoo.custom_model(**overrides)
    length = args.seq_len or zoo.SIZES["seq_len"]
    batch = zoo.SIZES["minibatch_per_chip"]
    variables = model.init(jax.random.PRNGKey(0), None)
    spec = ModelSpec(
        model=model, dataset_fn=zoo.dataset_fn, loss=zoo.loss,
        optimizer=zoo.optimizer,
    )
    worker = Worker(
        0, None, spec, minibatch_size=batch, local_updates=args.steps
    )
    worker._maybe_init_flat_from_tree(variables["params"])
    flat = jax.ShapeDtypeStruct(worker._flat.shape, worker._flat.dtype)
    worker._flat = None
    aux = {k: v for k, v in variables.items() if k != "params"}
    leaves = carries_leaves(worker._template)
    state = jax.eval_shape(zoo.optimizer().init, flat)
    if leaves:  # the model and each moment of its shape as the tree
        state = jax.tree_util.tree_map(
            lambda a: worker._template if a.shape == flat.shape else a, state,
            is_leaf=lambda a: hasattr(a, "shape"),
        )
    carried = (worker._template if leaves else flat, state)
    tokens = jax.ShapeDtypeStruct((args.steps, batch, length), jnp.int32)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree,
        )

    kept = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = worker._build_local_window_fn().lower(
            *on_chip((*carried, aux, tokens, tokens))
        ).compile()
    finally:
        jax.default_backend = kept
    memory = compiled.memory_analysis()
    found = {
        "arguments": memory.argument_size_in_bytes,
        "outputs": memory.output_size_in_bytes,
        "aliased": memory.alias_size_in_bytes,
        "temporaries": memory.temp_size_in_bytes,
        "code": memory.generated_code_size_in_bytes,
    }
    found["program_alone"] = (
        found["arguments"] + found["outputs"] - found["aliased"]
        + found["temporaries"] + found["code"]
    )
    found["with_base_flat"] = found["program_alone"] + 4 * flat.shape[0]
    print(json.dumps({
        "config": args.config, "overrides": overrides, "steps": args.steps,
        "tokens": [batch, length], "parameters": flat.shape[0],
        "carry": "leaves" if leaves else "flat", **found,
        "kernels": hlo_scopes.kernels(compiled.as_text()),
    }))


if __name__ == "__main__":
    main()
