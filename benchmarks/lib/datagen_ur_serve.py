"""Seeded inputs of the served Universal Recommender deployment: the
indicators of every event type, each user's events, the popularity ranking
and which shape each request of a schedule has. Nothing here imports the
program.

What is the SHAPE of the work is the same in every seed, as in the retrain
cells: how many events each user has, which of them are buys and the
popularity RANK of each event's item come from the configuration's
``shape_seed``. ``--seed`` draws which item holds which rank, the
correlators and scores of every row, the rows' lengths, the items'
categories, the times in the store and what the requests ask for. A posting
list is about as long as its item is popular, so a user's query reads about
the same number of postings in every seed while every id and score differs.

The indicators are drawn, not counted (set-up cannot count co-occurrences
over 9.4M x 9.4M): block by block on the default device, one jitted program.
A row's correlators are ``maxCorrelatorsPerItem`` draws of a zipfian item
popularity (``correlator_zipf_s``: the continuous law ``x**-s`` on [1, n + 1)
cut to whole ranks), repeats dropped, in random order; ``short_row_share``
of the rows are cut to a length drawn uniformly below the width; what a row
lacks is -1, at its end, as ``cco_indicators_multi`` leaves the rows of
rare items. Scores are positive float32 and fall along a row.
"""

from __future__ import annotations

import numpy as np

import datagen
import datagen_ecomm

#: rows drawn a device program
BLOCK_ROWS = 1 << 18
#: threads of a pass over the blocks (`each_block`)
THREADS = 4
SHAPES = ("user", "filter", "boost", "item", "blacklist", "unknown")
(EVENT_STREAM, SCRAMBLE_STREAM, INDICATOR_STREAM, BODY_STREAM,
 SHAPE_STREAM, BLACKLIST_STREAM) = 21, 22, 23, 24, 25, 26
#: multipliers of the rank -> item scramble: odd primes under 2**32 /
#: 9.4M that do not divide it, so ``rank * a`` stays in 32 bits
MULTIPLIERS = (197, 199, 211, 223, 227)


def zipf_ranks(u: np.ndarray, n: int, s: float) -> np.ndarray:
    """Ranks in [0, n) of uniforms ``u`` in [0, 1) under the continuous law
    ``x**-s`` on [1, n + 1), cut to whole numbers (s < 1). The same
    arithmetic as the device program below, in float64."""
    e = 1.0 - s
    x = (((n + 1.0) ** e - 1.0) * np.asarray(u, np.float64) + 1.0) ** (1 / e)
    return np.minimum(x.astype(np.int64) - 1, n - 1)


def scramble(cfg: dict, seed: int):
    """rank -> item row, a bijection of [0, n_items) drawn from the seed:
    ``(rank * a + b) % n_items`` with ``a`` coprime to ``n_items``. The
    popular items are then not the rows 0, 1, 2..."""
    n = int(cfg["n_items"])
    rng = np.random.default_rng([int(seed), SCRAMBLE_STREAM])
    fit = [a for a in MULTIPLIERS if np.gcd(a, n) == 1 and a * n < 2 ** 32]
    a = int(fit[rng.integers(len(fit))]) if fit else 1
    b = int(rng.integers(n))
    return a, b


def items_of(ranks, cfg: dict, seed: int) -> np.ndarray:
    a, b = scramble(cfg, seed)
    return ((np.asarray(ranks, np.int64) * a + b) % cfg["n_items"]
            ).astype(np.int32)


def user_events(cfg: dict) -> dict:
    """Every seed's events, user by user, as RANKS: ``offsets`` int64[n_users
    + 1] into ``rank`` int32[E] (the popularity rank of each event's item)
    and ``buy`` bool[E]. Events a user: one and a log-normal share of the
    rest, clipped at ``seen_limit`` (under the program's read limit, so no
    read is cut short), as the e-commerce sibling; the ranks zipfian at
    ``event_zipf_s``: a shop's visitors view what is popular."""
    rng = np.random.default_rng([int(cfg["shape_seed"]), EVENT_STREAM])
    n_users = cfg["n_users"]
    counts = np.minimum(datagen.degree_sequence(
        n_users, int(round(cfg["events_per_user"] * n_users)),
        cfg["events_sigma"], rng), cfg["seen_limit"])
    offsets = np.concatenate([[0], np.cumsum(counts)])
    rank = zipf_ranks(rng.random(offsets[-1]), cfg["n_items"],
                      cfg["event_zipf_s"]).astype(np.int32)
    return {"offsets": offsets, "rank": rank,
            "buy": rng.random(offsets[-1]) < cfg["buy_share"]}


def history_of(ev: dict, user: int) -> dict:
    """{event name: item rows} of one user, as the store holds them
    (``ev["item"]``: the seed's items of the ranks)."""
    lo, hi = ev["offsets"][user], ev["offsets"][user + 1]
    item, buy = ev["item"][lo:hi], ev["buy"][lo:hi]
    return {"buy": item[buy], "view": item[~buy]}


def popularity(cfg: dict, ev: dict) -> np.ndarray:
    """float32[n_items]: the buys of each item (the template's default
    ``popular`` ranking counts the primary event)."""
    return np.bincount(ev["item"][ev["buy"]],
                       minlength=cfg["n_items"]).astype(np.float32)


# -- the indicators ----------------------------------------------------------


def _draw_block(cfg: dict, rows: int):
    """The jitted program of one block: (key, the scramble) -> (idx int32,
    score float32) of ``rows`` rows."""
    import jax
    import jax.numpy as jnp

    n, k = int(cfg["n_items"]), int(cfg["maxCorrelatorsPerItem"])
    e = 1.0 - float(cfg["correlator_zipf_s"])
    span = np.float32((n + 1.0) ** e - 1.0)
    short = float(cfg["short_row_share"])

    @jax.jit
    def draw(key, a, b):
        k_rank, k_order, k_len, k_top, k_fall = jax.random.split(key, 5)
        u = jax.random.uniform(k_rank, (rows, k), jnp.float32)
        x = (span * u + 1.0) ** np.float32(1 / e)
        rank = jnp.clip(x.astype(jnp.int32) - 1, 0, n - 1)
        rank = jnp.sort(rank, axis=1)
        repeat = jnp.concatenate([
            jnp.zeros((rows, 1), bool), rank[:, 1:] == rank[:, :-1]],
            axis=1)
        # a random order, the repeats behind every first
        order = jnp.where(repeat, 2.0, jax.random.uniform(
            k_order, (rows, k), jnp.float32))
        _, rank, repeat = jax.lax.sort((order, rank, repeat), dimension=1,
                                       num_keys=1)
        u_len = jax.random.uniform(k_len, (rows, 2), jnp.float32)
        length = jnp.where(u_len[:, 0] < short,
                           (u_len[:, 1] * k).astype(jnp.int32), k)
        slot = jnp.arange(k, dtype=jnp.int32)[None, :]
        live = ~repeat & (slot < length[:, None])
        item = ((rank.astype(jnp.uint32) * a + b) % np.uint32(n)
                ).astype(jnp.int32)
        top = 4.0 + 60.0 * jax.random.uniform(
            k_top, (rows, 1), jnp.float32) ** 2
        fall = jnp.cumprod(0.8 + 0.2 * jax.random.uniform(
            k_fall, (rows, k), jnp.float32), axis=1)
        return (jnp.where(live, item, -1),
                jnp.where(live, top * fall, 0.0).astype(jnp.float32))

    return draw


def block_drawer(cfg: dict, seed: int):
    """(blocks an event type, ``one(event number, block) -> (first row, idx,
    score)`` as host arrays, the last block cut to the catalog). A block is
    a function of (seed, event, block) alone, so a pass after the first
    draws the same again without holding 7.5 GB meanwhile. ``one`` may be
    called from several threads."""
    import jax

    n = int(cfg["n_items"])
    rows = min(BLOCK_ROWS, n)
    draw = _draw_block(cfg, rows)
    a, b = (np.uint32(v) for v in scramble(cfg, seed))
    base = datagen._key(seed, INDICATOR_STREAM)

    def one(event: int, blk: int):
        lo = blk * rows
        key = jax.random.fold_in(jax.random.fold_in(base, event), blk)
        idx, score = (np.asarray(x)[:n - lo] for x in draw(key, a, b))
        return lo, idx, score

    return -(-n // rows), one


def indicator_blocks(cfg: dict, seed: int, event: int):
    """(first row, idx, score) of every block of one event type's
    indicators, in row order."""
    blocks, one = block_drawer(cfg, seed)
    for blk in range(blocks):
        yield one(event, blk)


def each_block(cfg: dict, seed: int):
    """``each(work)``: ``work(event number, first row, idx, score)`` for
    every block of every event type's indicators, on `THREADS` threads in
    no order: one block's draw on the device, its fetch and what ``work``
    does in numpy run beside the other threads' (all of them let go of the
    interpreter's lock). At full size the reference's pass over 2 x 36
    blocks took 32 s a block at a time and takes about 12 on four threads
    (my chip runs, PR 42)."""
    import concurrent.futures

    blocks, one = block_drawer(cfg, seed)
    jobs = [(e, blk) for e in range(len(cfg["eventNames"]))
            for blk in range(blocks)]

    def each(work) -> None:
        run = lambda job: work(job[0], *one(*job))
        run(jobs[0])  # the draw's one compile, before the threads ask for it
        with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(run, jobs[1:]))

    return each


def indicators(cfg: dict, seed: int) -> tuple[dict, dict]:
    """({event name: (idx int32[n_items, K], score float32[n_items, K])},
    {event name: int32[n_items] how many rows name each item: the length of
    its posting list})."""
    import threading

    n, k = int(cfg["n_items"]), int(cfg["maxCorrelatorsPerItem"])
    out = [(np.empty((n, k), np.int32), np.empty((n, k), np.float32))
           for _ in cfg["eventNames"]]
    counts = [np.zeros(n + 1, np.int64) for _ in out]
    lock = threading.Lock()

    def keep(event: int, lo: int, idx_b, score_b) -> None:
        idx, score = out[event]
        idx[lo:lo + len(idx_b)] = idx_b
        score[lo:lo + len(idx_b)] = score_b
        found = np.bincount(idx_b.ravel() + 1, minlength=n + 1)
        with lock:
            counts[event] += found

    each_block(cfg, seed)(keep)
    return (dict(zip(cfg["eventNames"], out)),
            {name: c[1:].astype(np.int32)
             for name, c in zip(cfg["eventNames"], counts)})


# -- which request has which shape -------------------------------------------


def shapes_of(traffic: dict, sched: dict) -> list[str]:
    """The shape of each row of a schedule. A row whose user the store has
    never seen (``loadgen.schedule`` marks them by the mix's own seed) is
    ``unknown``; ``shape_shares`` of ALL rows are then laid over the others
    by the rank of their arrival gap, in an order drawn from the mix's
    ``arrival_seed``, and what is left asks with ``user`` alone. The same
    place of the arrival cycle so has the same shape in every seed."""
    n = len(sched["due"])
    known = np.array([u.isdigit() for u in sched["user"]])
    mix = np.random.default_rng([int(traffic["arrival_seed"]), SHAPE_STREAM])
    turn = mix.permutation(n)[datagen_ecomm.cycle_ranks(sched["due"])]
    rows = np.flatnonzero(known)
    rows = rows[np.argsort(turn[rows], kind="stable")]
    out = np.where(known, "user", "unknown").astype(object)
    at = 0
    for shape in SHAPES[1:5]:
        count = int(round(float(traffic["shape_shares"][shape]) * n))
        out[rows[at:at + count]] = shape
        at += count
    return out.tolist()


def users_asking(traffic: dict, sched: dict, shape: str) -> list[int]:
    """The distinct user rows of the schedule's requests of one shape (a
    shape the store's users have), in order of first arrival."""
    return list(dict.fromkeys(
        int(user) for user, has in zip(sched["user"],
                                       shapes_of(traffic, sched))
        if has == shape))


def request_fields(cfg: dict, traffic: dict, seed: int, sched: dict,
                   own_top: dict | None = None) -> list[dict]:
    """What each row of the schedule asks beside ``num``, as item rows and
    category indices: ``{"shape", "user": row or None, "category": c,
    "bias", "item": row, "blacklist": rows}`` (the keys its shape has).
    ``filter`` and ``boost`` name ONE category, drawn by size; ``item`` an
    item drawn by popularity (a product page); ``blacklist`` 1-20 ids of
    the user's OWN unfiltered best ``black_list_top``, which ``own_top``
    ({user row: rows, best first}) holds for every one of `users_asking`
    ``blacklist`` (fewer ids only where fewer items score for the user).
    Without ``own_top`` the blacklists are left out: every history and
    query item is then known already, which is what the reference's one
    pass over the indicators needs before it can say whose best items are
    which. Drawn from ``--seed``, the blacklists from a stream of their
    own, so that both calls draw the same categories and items."""
    rng = np.random.default_rng([int(seed), BODY_STREAM])
    black = np.random.default_rng([int(seed), BLACKLIST_STREAM])
    shares = datagen_ecomm.category_shares(cfg)
    lo_b, hi_b = traffic["black_list_len"]
    n = cfg["n_items"]
    out = []
    for user, shape in zip(sched["user"], shapes_of(traffic, sched)):
        q = {"shape": shape, "user": int(user) if user.isdigit() else None}
        if shape in ("filter", "boost"):
            q["category"] = int(rng.choice(len(shares), p=shares))
            q["bias"] = -1.0 if shape == "filter" else float(
                traffic["boost_bias"])
        elif shape == "item":
            q["user"] = None
            q["item"] = int(items_of(zipf_ranks(
                rng.random(), n, cfg["event_zipf_s"]), cfg, seed))
        elif shape == "blacklist" and own_top is not None:
            size = int(black.integers(lo_b, hi_b + 1))
            q["blacklist"] = black.permutation(np.asarray(
                own_top[q["user"]], np.int64))[:size]
        out.append(q)
    return out
