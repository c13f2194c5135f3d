"""FLOPs one training sample requires, from shapes alone.

Forward plus backward, no recomputation: the backward pass of a matrix
multiplication or convolution costs two more of the same size (one for
the input's gradient, one for the weight's), so training = 3 x forward.
One multiply-accumulate = 2 FLOPs. Elementwise work (normalisation,
activations, softmax, the optimizer) is not counted: it is a few
percent and the MXU peak it is divided by does not serve it.

A configuration names its formula in `config.json` (`"flops":
{"formula": ...}`); one the table lacks is looked for as
`flops_per_sample(sizes)` in a `flops.py` beside that file, so a new
model brings its own arithmetic as a new file.
"""

import os

from benchmark.harness.manifest import load_module


def _same(size, stride):
    return -(-size // stride)  # ceil: 'SAME' padding


def resnet_bottleneck(sizes):
    """ResNet (He et al. 2015, Table 1) as `models/resnet50_subclass`
    builds it: 7x7/2 stem, 3x3/2 max pool, stages of 1x1 -> 3x3 -> 1x1
    bottlenecks with the stride on the 3x3 and a 1x1 projection
    shortcut where the shape changes, global pool, dense head."""
    h, w, cin = sizes["image_shape"]
    macs = 0

    def conv(h, w, k, cin, cout, stride):
        nonlocal macs
        h, w = _same(h, stride), _same(w, stride)
        macs += h * w * k * k * cin * cout
        return h, w

    h, w = conv(h, w, 7, cin, sizes["stem_width"], 2)
    h, w = _same(h, 2), _same(w, 2)  # max pool
    cin = sizes["stem_width"]
    for i, blocks in enumerate(sizes["stage_sizes"]):
        width = sizes["stem_width"] * 2**i
        out = width * sizes["bottleneck_expansion"]
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            if cin != out or stride != 1:
                conv(h, w, 1, cin, out, stride)  # projection shortcut
            conv(h, w, 1, cin, width, 1)
            h, w = conv(h, w, 3, width, width, stride)
            conv(h, w, 1, width, out, 1)
            cin = out
    macs += cin * sizes["num_classes"]
    return 3 * 2 * macs


def dense_transformer(sizes):
    """Decoder-only dense transformer, one sample = `seq_len` tokens.
    Per token and layer: q, k, v, o (4 d^2) and the two MLP matrices
    (2 d d_ff); the untied head (d V) once; the embedding is a lookup.
    Attention scores and their product with V are counted CAUSAL — a
    token attends to (seq_len + 1) / 2 positions on average, 2 matmuls
    of d each — although XLA's unfused attention computes the masked
    half too: work the algorithm does not require is not credited."""
    d, layers = sizes["hidden_size"], sizes["num_hidden_layers"]
    s = sizes["seq_len"]
    weights = layers * (4 * d * d + 2 * d * sizes["intermediate_size"])
    weights += d * sizes["vocab_size"]
    attention = layers * 2 * d * (s + 1) / 2
    return 3 * 2 * (weights + attention) * s


FORMULAS = {
    "resnet_bottleneck": resnet_bottleneck,
    "dense_transformer": dense_transformer,
}


def flops_per_sample(sizes, config_dir=None):
    name = sizes["flops"]["formula"]
    if name in FORMULAS:
        return FORMULAS[name](sizes)
    path = os.path.join(config_dir or "", "flops.py")
    if not os.path.isfile(path):
        raise KeyError(f"no FLOP formula {name!r}, and no {path}")
    return load_module(path).flops_per_sample(sizes)
