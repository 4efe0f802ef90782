"""Least work of a round's latent attention under the program's scope
``attention`` (scores, mask, softmax, values; forward and backward): the
causal pairs of every layer, with q.k over the part without positions and
the shared part with them (192 at the published widths) and p.v over the
values (128).  Padding q and k to whole lanes is the kernels' choice, not
work: it shows as a lower share."""

from perfbench.kernels.attention import visible_pairs


def ops_bytes(contexts, length, layers, heads, nope, rope, v_dim):
    """FLOPs: 2 a multiply-add, (nope + rope) for q.k and v_dim for p.v a
    causal pair a head, forward; backward twice that.  Bytes, f32: one read
    of q (heads x (nope + rope)), k's part without positions (heads x
    nope), the ONE shared key head with positions (rope), v (heads x v_dim)
    and one write of the output (heads x v_dim) a layer, and as much again
    for their gradients."""
    pairs = layers * visible_pairs(length)
    ops = 3.0 * 2 * contexts * heads * (nope + rope + v_dim) * pairs
    row = heads * (nope + rope) + heads * nope + rope + 2 * heads * v_dim
    nbytes = 2.0 * 4 * contexts * layers * length * row
    return ops, nbytes
