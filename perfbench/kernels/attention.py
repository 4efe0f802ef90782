"""Least work of a round's attention under the program's scope ``attention``
(scores, mask, softmax, values; forward and backward): counted over the pairs
a causal layer has to compute, so a window layer that computes every causal
pair shows as waste, not as work."""


def visible_pairs(length, window=None):
    """(query, key) pairs with key <= query and, under a window, query - key
    < window: 33,558,528 at 8,192 without one, 25,167,872 with 4,096."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def ops_bytes(contexts, length, heads, kv_heads, head_dim, global_layers,
              window_layers, window):
    """FLOPs: 4 a visible pair a head dimension (q.k and p.v, 2 a
    multiply-add) forward, backward twice that.  Bytes: one read of q, k, v
    and one write of the output a layer, and as much again for their
    gradients, f32."""
    pairs = (global_layers * visible_pairs(length)
             + window_layers * visible_pairs(length, window))
    ops = 3.0 * 4 * contexts * heads * head_dim * pairs
    layers = global_layers + window_layers
    rows = 2 * heads + 2 * kv_heads          # q, out, k, v
    nbytes = 2.0 * 4 * contexts * layers * length * rows * head_dim
    return ops, nbytes
