"""Krum as the reference defines it (reference defences.py:23-42, the
form defenses/oracle.py ``np_krum_select`` spells out): the input row whose
summed Euclidean distance to its k = n - f nearest other rows is least.

Kept here, not read from the program: the counts behind
``defense_roofline_pct`` and the host reference that decides ``correct``.
"""

import numpy as np

FULL_CHECK_ROWS = 1024   # above this the f64 Gram of all rows is too slow
SAMPLED_ROWS = 128       # for every run; a seeded sample of rows is scored
# The device scores rows in f32 from a Gram at Precision.HIGHEST: a row's
# score is off by ~1e-6 relative, so the device's winner may trail the f64
# winner by that much.  A bf16 Gram (~4e-3) or a dropped term fails.
TIE_RTOL = 1e-5
# The host never holds the whole (n, d) matrix: it pulls blocks of whole
# columns of about this many f32 bytes (a block's f64 copy is twice that),
# whatever n and d are.  Every quantity below is a sum or a conjunction
# over column blocks, so the block size changes no result.
BLOCK_BYTES = 256 * 2**20


def ops_bytes(n, d, f):
    """One call's least work: the n x n Gram over d (2 n^2 d FLOP) and one
    read of the (n, d) matrix plus one write of the (n, n) distances, f32."""
    return 2.0 * n * n * d, 4.0 * (n * d + n * n)


def column_blocks(G):
    """(first column, the (n, width) block as a host array) over the
    columns of ``G``, a device or a host array."""
    n, d = G.shape
    width = max(1, BLOCK_BYTES // (4 * n))
    for lo in range(0, d, width):
        yield lo, np.asarray(G[:, lo:lo + width])


def scores(G, rows, k):
    """f64 Krum scores of ``rows`` against all rows of G (f32, (n, d))."""
    n = G.shape[0]
    sq, cross = np.zeros(n), np.zeros((len(rows), n))
    for _, block in column_blocks(G):
        B = block.astype(np.float64)
        sq += np.einsum("nd,nd->n", B, B)
        cross += B[rows] @ B.T
    D2 = sq[rows][:, None] + sq[None, :] - 2.0 * cross
    D = np.sqrt(np.maximum(D2, 0.0))
    D[np.arange(len(rows)), rows] = np.inf      # a row is not its own peer
    return np.partition(D, k - 1, axis=1)[:, :k].sum(axis=1)


def rows_equal_to(G, agg):
    """Indices of the rows of G that equal ``agg`` in every column."""
    same = np.ones(G.shape[0], bool)
    for lo, block in column_blocks(G):
        same &= (block == agg[None, lo:lo + block.shape[1]]).all(axis=1)
    return np.flatnonzero(same)


def check(G, n, f, agg, seed=0):
    """Is the device's aggregate the reference's?  ``G`` the (n, d) wire
    matrix as the device made it (still on the device: it is read in
    column blocks) and ``agg`` the defense's output on the host."""
    winners = rows_equal_to(G, agg)
    if winners.size == 0:
        return {"ok": False, "why": "the aggregate is not an input row",
                "compared": {"aggregate_not_an_input_row": [1, 0]}}
    got, k = int(winners[0]), n - f
    if n <= FULL_CHECK_ROWS:
        rows, mode = np.arange(n), "all_rows"
    else:
        rng = np.random.default_rng(seed)
        rows = np.unique(np.append(
            rng.choice(n, SAMPLED_ROWS, replace=False), got))
        mode = f"sample_of_{len(rows)}_rows"
    s = scores(G, rows, k)
    best = int(rows[np.argmin(s)])
    s_got, s_best = float(s[rows == got][0]), float(s.min())
    gap = (s_got - s_best) / s_best
    verdict = ("exact_index" if best == got else
               "same_row" if best in winners else
               "tie_band" if gap <= TIE_RTOL else "wrong_row")
    return {"ok": verdict != "wrong_row", "verdict": verdict, "mode": mode,
            "device_winner": got, "reference_winner": best,
            "identical_winner_rows": int(winners.size),
            "relative_score_gap": gap,
            "compared": {"aggregate_not_an_input_row": [0, 0],
                         "defense_score_gap": [gap, TIE_RTOL]}}
