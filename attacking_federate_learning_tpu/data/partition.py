"""Client data partitioning.

The reference partitions via ``DistributedSampler(num_replicas=users_count,
rank=user_id)`` (reference user.py:49-54): one global permutation, padded to a
multiple of n by wrapping, then strided by rank — an IID equal shard per
client.  Because the reference never advances the sampler epoch, the
permutation is identical on every pass (SURVEY.md §2.4 #13); we reproduce
that by computing the shard matrix once per experiment.

The partition is materialized as an int32 index matrix ``shards`` of shape
(n_clients, shard_len); a round's batch for all clients at once is

    idx = shards[:, (t*B + arange(B)) % shard_len]          # (n, B)
    batch_x = X2d[idx].reshape((n, B) + sample_shape)       # one row gather
    batch_y = Y[idx]

which keeps shapes static under jit (the reference's DataLoader yields a
short final batch instead; wrap-around is the jit-friendly equivalent).
``X2d`` is the training set stored (N, F), one sample a contiguous row:
the gather then moves whole rows, and the sample shape comes back after
it (core/engine.py ``_gather_batches``).  Indexing the set in its sample
shape, ``X[idx]`` with X (N, C, H, W), is the same tensor but lets the TPU
compiler give the gather its consumer's layout -- sample axis minor --
and move the batch one element at a time (PERF.md section 6, PR 26).

Also provides a Dirichlet label-skew partitioner for non-IID experiments
(no reference analog — the reference is IID-only).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def iid_shards(n_examples: int, n_clients: int, seed: int) -> np.ndarray:
    """DistributedSampler-equivalent IID shards: (n_clients, shard_len)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n_examples)
    shard_len = -(-n_examples // n_clients)  # ceil
    total = shard_len * n_clients
    padded = np.concatenate([perm, perm[: total - n_examples]])
    # rank r takes padded[r::n_clients] — the sampler's strided subsample.
    return np.stack([padded[r::n_clients] for r in range(n_clients)]).astype(
        np.int32)


def dirichlet_shards(labels: np.ndarray, n_clients: int, alpha: float,
                     seed: int) -> np.ndarray:
    """Label-skew non-IID shards via per-class Dirichlet allocation.

    Shards are equalized to a common length by wrapping each client's own
    indices so the result is still a dense (n_clients, shard_len) matrix.
    """
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    per_client: list = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx = np.flatnonzero(labels == c)
        rng.shuffle(idx)
        props = rng.dirichlet(np.full(n_clients, alpha))
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for client, chunk in enumerate(np.split(idx, cuts)):
            per_client[client].extend(chunk.tolist())
    shard_len = max(1, max(len(s) for s in per_client))
    out = np.empty((n_clients, shard_len), np.int32)
    for i, s in enumerate(per_client):
        if not s:  # degenerate client: give it one wrapped global sample
            s = [int(rng.integers(len(labels)))]
        reps = -(-shard_len // len(s))
        out[i] = np.tile(np.array(s, np.int32), reps)[:shard_len]
    return out


def client_style_params(n_clients: int, strength: float,
                        seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-client affine style parameters for 'femnist_style' partition.

    FEMNIST's defining non-IIDness is *feature/style* shift — each
    writer's pen, pressure, and slant shifts the input distribution even
    when the label mix is identical (SURVEY.md §7.2 M4 names
    "FEMNIST/Dirichlet"; Dirichlet covers the label axis only).  Real
    FEMNIST cannot be downloaded on this zero-egress box, so the
    air-gapped stand-in transforms each client's view of the shared
    pool: client i sees ``a_i * x + b_i`` — a per-writer
    contrast/brightness transform, the first-order model of writer
    style.  Drawn once per experiment from the config seed:

        a_i = 1 + strength * u1   (u1 ~ U[-1, 1])   # contrast
        b_i = strength/2 * u2     (u2 ~ U[-1, 1])   # brightness

    Unlike Dirichlet label skew, this gives HONEST clients' gradients
    systematic structure (each client's input statistics differ), which
    is the adversarial condition distance-based defenses (Krum/Bulyan)
    are weakest under — label skew alone is kind to them.
    """
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xFE30]))
    a = 1.0 + strength * rng.uniform(-1.0, 1.0, n_clients)
    b = 0.5 * strength * rng.uniform(-1.0, 1.0, n_clients)
    return a.astype(np.float32), b.astype(np.float32)


def make_shards(partition: str, labels: np.ndarray, n_clients: int,
                seed: int, dirichlet_alpha: float = 0.5) -> np.ndarray:
    if partition in ("iid", "femnist_style"):
        # femnist_style shares the IID index assignment: its non-IIDness
        # lives in the per-client input transform (client_style_params),
        # not in which examples a client holds.
        return iid_shards(len(labels), n_clients, seed)
    if partition == "dirichlet":
        return dirichlet_shards(labels, n_clients, dirichlet_alpha, seed)
    raise ValueError(f"Unknown partition {partition!r}")


def round_batch_indices(shards, round_idx: int, batch_size: int):
    """(n_clients, B) gather indices for one round, cycling each shard.

    Mirrors the reference's infinite ``cycle`` over each client's loader
    (reference user.py:11-14, :55) with wrap-around instead of short final
    batches, so shapes stay static under jit.
    """
    shard_len = shards.shape[1]
    offs = (round_idx * batch_size + jnp.arange(batch_size)) % shard_len
    return shards[:, offs]
