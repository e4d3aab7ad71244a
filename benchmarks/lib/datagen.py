"""Seeded inputs for the benchmark: ratings for the retrain cells, factors
for the serve cells. Nothing here imports the program.

Ratings: the two degree sequences (ratings per user, ratings per item) come
from the configuration's own ``shape_seed`` and are the same for every
``--seed``; ``--seed`` pairs the user stubs with the item stubs by a
permutation and draws the ratings. The layout plan of the program depends on
the per-row counts only, so every seed of a cell has the same plan and the
same executable, while the slab contents differ per seed.
"""

from __future__ import annotations

import numpy as np


def degree_sequence(n_rows: int, total: int, sigma: float,
                    rng: np.random.Generator) -> np.ndarray:
    """``n_rows`` degrees >= 1 that sum to ``total``: one each, the rest
    multinomial over log-normal weights (heavy-tailed for larger sigma)."""
    if total < n_rows:
        raise ValueError(f"{total} ratings cannot give {n_rows} rows one each")
    w = rng.lognormal(0.0, sigma, n_rows)
    return 1 + rng.multinomial(total - n_rows, w / w.sum())


def degrees(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """(ratings per user, ratings per item) of a configuration: a function
    of its counts and ``shape_seed`` alone."""
    rng = np.random.default_rng(int(cfg["shape_seed"]))
    du = degree_sequence(cfg["n_users"], cfg["n_ratings"],
                         cfg["user_degree_sigma"], rng)
    di = degree_sequence(cfg["n_items"], cfg["n_ratings"],
                         cfg["item_degree_sigma"], rng)
    return du, di


def ratings(cfg: dict, seed: int, degs=None
            ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The COO triple (user, item, rating) of one seed: int32, int32,
    float32 in 0.5 .. 5.0. Same degrees for every seed (``degs``: what
    ``degrees(cfg)`` gave, to save drawing them twice), another pairing."""
    du, di = degs if degs is not None else degrees(cfg)
    rng = np.random.default_rng(int(seed))
    u = np.repeat(np.arange(cfg["n_users"], dtype=np.int32), du)
    i = np.repeat(np.arange(cfg["n_items"], dtype=np.int32), di)
    i = i[rng.permutation(len(i))]
    r = rng.integers(1, 11, len(u), dtype=np.uint8) * np.float32(0.5)
    return u, i, r


def _key(seed: int, stream: int):
    import jax

    # a seed may pass 2**31: split it, so that no 32-bit conversion cuts it
    seed = int(seed)
    k = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 31), stream)


def factors(n_rows: int, rank: int, seed: int, stream: int,
            block_rows: int = 1 << 19) -> np.ndarray:
    """[n_rows, rank] float32 N(0, 1/rank) as a host array, drawn on the
    default device from the seed in blocks of ``block_rows`` (one jitted
    program; a block is 256 MiB at rank 128, so the draw leaves no mark on
    the device's peak memory beside a multi-GB catalog)."""
    import jax
    import jax.numpy as jnp

    scale = np.float32(1.0 / np.sqrt(rank))
    draw = jax.jit(lambda k: jax.random.normal(
        k, (block_rows, rank), jnp.float32) * scale)
    key = _key(seed, stream)
    out = np.empty((n_rows, rank), np.float32)
    for b, lo in enumerate(range(0, n_rows, block_rows)):
        hi = min(lo + block_rows, n_rows)
        out[lo:hi] = np.asarray(draw(jax.random.fold_in(key, b)))[:hi - lo]
    return out


USER_STREAM, ITEM_STREAM = 1, 2
