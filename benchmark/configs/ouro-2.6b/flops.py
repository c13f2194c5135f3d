"""FLOPs one training sample of the looped LM requires, from shapes
alone (`harness/flops.py`'s rules: 3 x forward, 2 FLOPs a
multiply-accumulate, elementwise work not counted, recomputation not
credited). The stack's weights are used `total_ut_steps` times a
token, and every pass is an exit with its own projection onto the
vocabulary held here and its own gate."""


def flops_per_sample(sizes):
    d, s = sizes["hidden_size"], sizes["seq_len"]
    passes, layers = sizes["total_ut_steps"], sizes["num_hidden_layers"]
    per_layer = 4 * d * d + 3 * d * sizes["intermediate_size"]  # q k v o; gate up down
    weights = passes * layers * per_layer
    exits = passes * (d * sizes["vocab_size"] + d)  # head and gate, every pass
    # causal: a token attends to (s + 1) / 2 positions on average, two
    # matmuls of d each (scores, and their product with V)
    attention = passes * layers * 2 * d * (s + 1) / 2
    return 3 * 2 * (weights + exits + attention) * s
