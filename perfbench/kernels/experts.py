"""Least work of a round's expert blocks under the program's scope
``experts`` (top-k, dispatch, the grouped products, combine; not the
router's matmul): the held experts over the (token, slot) pairs that even
routing sends them."""


def ops_bytes(contexts, length, layers, top_k, held, experts, hidden, width):
    """FLOPs: a routed pair passes gate, up and down (3 x hidden x width
    multiply-adds, 2 FLOP each) forward, backward twice that.  Bytes, f32:
    the held experts' weights read and their gradients written once a client
    step, and the routed rows in and out, forward and backward."""
    routed = length * top_k * held / experts        # a context, a layer
    ops = 3.0 * 2 * contexts * layers * routed * 3 * hidden * width
    weights = 2.0 * 4 * held * 3 * hidden * width
    rows = 2.0 * 2 * 4 * routed * hidden
    return ops, contexts * layers * (weights + rows)
