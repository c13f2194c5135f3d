"""Seeded training data as RecordIO, one general writer per kind.

A configuration's `config.json` names the kind and its parameters
(`"data": {"kind": "image", ...}`); the records are a function of
those and of `--seed` alone, and are learnable, so a run can check that
the loss falls. A directory is written once per (kind, parameters,
seed) under `<checkout>/.bench_cache/data/` and reused: the job is
given epochs over it, never a length sized from a guessed rate.
`"shards": n` makes the directory hold n files of `records` records
each: the file written from the seed and n - 1 hard links to it, so
that an epoch holds several tasks of the program's default size
without gigabytes written in every checkout (the input path reads and
decodes every record of every shard all the same).

A kind this table lacks is looked for as `write_records(path, seed,
sizes)` in a `datagen.py` beside the configuration's file.
"""

import hashlib
import json
import os
import shutil

import numpy as np

from benchmark.harness.manifest import load_module

CACHE = os.path.join(".bench_cache", "data")


def write_image_records(path, seed, data):
    """uint8 images whose mean rises with the label (the layout of
    `record_codec.encode_image_record`: int64 label | pixels), as
    `write_synthetic_image_records` makes them but with the class
    means spread over any number of classes, and the noise drawn once
    for a block of images instead of once for each."""
    from elasticdl_tpu.data.recordio import RecordIOWriter

    rng = np.random.default_rng(seed)
    shape, classes = tuple(data["shape"]), data["classes"]
    block = 64
    noise = rng.normal(0.0, 25.0, size=(block,) + shape).astype(np.float32)
    labels = rng.integers(classes, size=data["records"])
    with RecordIOWriter(path) as w:
        for i, label in enumerate(labels):
            mean = 40.0 + 175.0 * label / max(classes - 1, 1)
            img = np.clip(noise[i % block] + mean, 0, 255).astype(np.uint8)
            w.write(np.int64(label).tobytes() + img.tobytes())


def write_token_records(path, seed, data):
    """Arithmetic sequences mod `alphabet`, stride 1 to 3 (the layout
    and the rule of `record_codec.write_learnable_token_records`: int32
    [seq_len + 1]). `alphabet` is the number of token ids the data
    use, at most the model's vocabulary: over all 50304 ids a few
    hundred steps see each id a handful of times and teach nothing,
    over a few hundred the loss falls from the first window on. The
    model, its shapes and its work are the same either way."""
    from elasticdl_tpu.data.recordio import RecordIOWriter

    rng = np.random.default_rng(seed)
    ramp = np.arange(data["seq_len"] + 1)
    alphabet = data["alphabet"]
    with RecordIOWriter(path) as w:
        for _ in range(data["records"]):
            start = int(rng.integers(alphabet))
            stride = int(rng.integers(1, 4))
            tokens = (start + stride * ramp) % alphabet
            w.write(np.ascontiguousarray(tokens, dtype=np.int32).tobytes())


KINDS = {"image": write_image_records, "tokens": write_token_records}


def _writer(kind, config_dir):
    if kind in KINDS:
        return KINDS[kind]
    path = os.path.join(config_dir, "datagen.py")
    if not os.path.isfile(path):
        raise KeyError(f"no data kind {kind!r}, and no {path}")
    return load_module(path).write_records


def ensure(root, sizes, config_dir, seed):
    """-> the directory holding this (data, seed)'s RecordIO files."""
    data = sizes["data"]
    key = hashlib.sha1(
        json.dumps(data, sort_keys=True).encode()
    ).hexdigest()[:12]
    final = os.path.join(root, CACHE, f"{data['kind']}-{key}-s{seed}")
    if os.path.isdir(final):
        return final
    tmp = f"{final}.writing-{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    first = os.path.join(tmp, "train.rio")
    _writer(data["kind"], config_dir)(first, seed, data)
    for i in range(1, int(data.get("shards", 1))):
        os.link(first, os.path.join(tmp, f"train-{i}.rio"))
    try:
        os.rename(tmp, final)
    except OSError:  # another run of this seed finished first
        shutil.rmtree(tmp, ignore_errors=True)
    return final
