"""Test fixture: a later PR's cost module, the count of its configuration's
work: ``(flops, bytes)`` of one optimizer step from the run's shapes."""


def fold(batch, heads, seq, hidden, layers, **_):
    head_dim = hidden // heads
    flops = 6.0 * 2.0 * (seq * seq / 2.0) * head_dim * heads * batch * layers
    return flops, 8.0 * batch * heads * seq * head_dim * 2.0 * layers


def model(tokens, layers, hidden, width, vocab, **shapes):
    matmuls = layers * (4 * 2.0 * hidden * hidden + 3 * 2.0 * hidden * width) + 2.0 * hidden * vocab
    params = layers * (4 * hidden * hidden + 3 * hidden * width) + 2 * vocab * hidden
    return 3.0 * tokens * matmuls + fold(layers=layers, hidden=hidden, **shapes)[0], params * 4.0 * 7.0
