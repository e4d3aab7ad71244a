"""Seeded inputs of the e-commerce deployment, beside ``datagen.factors``:
each item's category, what the event store holds before the window, and
which rule each request of a schedule carries. Nothing here imports the
program.

A forbidden item must lie where the unfiltered answer would show it, or the
comparison guards nothing: with random factors a random seen item is never in
a top 10 of 9.4M. So the hottest users of the mix's zipf see items of their
OWN top ``TOP`` (``top_items``: float32 scores on the default device), and
one item of each of the hottest ``withdrawn_hot_users``' top is withdrawn.
"""

from __future__ import annotations

import numpy as np

import datagen

#: the data set's 24 top-level categories, largest first
CATEGORIES = (
    "Books", "Electronics", "Movies and TV", "CDs and Vinyl",
    "Clothing, Shoes and Jewelry", "Home and Kitchen", "Kindle Store",
    "Sports and Outdoors", "Cell Phones and Accessories",
    "Health and Personal Care", "Toys and Games", "Video Games",
    "Tools and Home Improvement", "Beauty", "Apps for Android",
    "Office Products", "Pet Supplies", "Automotive",
    "Grocery and Gourmet Food", "Patio, Lawn and Garden", "Baby",
    "Digital Music", "Musical Instruments", "Amazon Instant Video")

#: how deep a hot user's own best items are taken
TOP = 16
RULES = ("none", "categories", "blackList", "whiteList")
CATEGORY_STREAM, EVENT_STREAM, BODY_STREAM, RULE_STREAM = 11, 12, 13, 14
#: loadgen.schedule's scramble of a zipf rank into a user id
SCRAMBLE = 2654435761


def category_shares(cfg: dict) -> np.ndarray:
    w = np.arange(1, len(CATEGORIES) + 1) ** -float(cfg["category_zipf_s"])
    return w / w.sum()


def categories(cfg: dict, seed: int) -> np.ndarray:
    """uint8[n_items]: the index into ``CATEGORIES`` of each item's one
    category, sizes zipfian (Books about a quarter at s = 1)."""
    rng = np.random.default_rng([int(seed), CATEGORY_STREAM])
    cdf = np.cumsum(category_shares(cfg))
    return np.searchsorted(cdf, rng.random(cfg["n_items"]) * cdf[-1]
                           ).astype(np.uint8)


def hot_users(cfg: dict) -> np.ndarray:
    """The user rows the mix's zipf asks for most, hottest first."""
    return (np.arange(cfg["hot_users"], dtype=np.int64) * SCRAMBLE
            ) % cfg["n_users"]


def top_items(item_factors: np.ndarray, vecs: np.ndarray,
              block: int = 1 << 17, chunk: int = 128) -> np.ndarray:
    """int32[len(vecs), TOP]: each vector's TOP best items by float32 score,
    best first. One jitted program an item block: the block's scores, each
    chunk's maximum, the TOP best chunks, and the TOP best of their
    candidates and of the best so far (exact: an item outside the TOP best
    chunks has TOP items above it)."""
    import jax
    import jax.numpy as jnp

    n_items, rank = item_factors.shape
    block = min(block, -(-n_items // chunk) * chunk)
    v = jax.device_put(np.asarray(vecs, np.float32))

    @jax.jit
    def merge(best_s, best_i, rows, lo, valid):
        s = jnp.matmul(v, rows.T, precision=jax.lax.Precision.HIGHEST)
        s = jnp.where(jnp.arange(block)[None, :] < valid, s, -jnp.inf)
        chunks = s.reshape(len(vecs), block // chunk, chunk)
        _, best_chunks = jax.lax.top_k(chunks.max(axis=2),
                                       min(TOP, block // chunk))
        cand = jnp.take_along_axis(chunks, best_chunks[:, :, None], axis=1)
        idx = lo + best_chunks[:, :, None] * chunk + jnp.arange(chunk)
        all_s = jnp.concatenate([best_s, cand.reshape(len(vecs), -1)], axis=1)
        all_i = jnp.concatenate([best_i, idx.reshape(len(vecs), -1)], axis=1)
        top_s, pos = jax.lax.top_k(all_s, TOP)
        return top_s, jnp.take_along_axis(all_i, pos, axis=1)

    best_s = jnp.full((len(vecs), TOP), -jnp.inf, jnp.float32)
    best_i = jnp.zeros((len(vecs), TOP), jnp.int32)
    rows = np.zeros((block, rank), np.float32)
    for lo in range(0, n_items, block):
        valid = min(block, n_items - lo)
        rows[:valid] = item_factors[lo:lo + valid]
        best_s, best_i = merge(best_s, best_i, rows, lo, valid)
    return np.asarray(best_i)


def events(cfg: dict, seed: int, hot_top: np.ndarray) -> dict:
    """What the event store holds before the window, user by user:
    ``offsets`` int64[n_users + 1] into ``item`` int32[E] (the target of each
    ``view`` or ``buy`` event) and ``buy`` bool[E]; and ``withdrawn``, the
    item ids of the ``unavailableItems`` constraint. Events a user: one and a
    log-normal share of the rest, clipped at the program's read limit, so a
    read never cuts a user's events short. A hot user's events target items
    of ``hot_top[j]``, all different, as far as they go; one slot of each of
    the hottest users' top is kept out of their events and withdrawn."""
    rng = np.random.default_rng([int(seed), EVENT_STREAM])
    n_users, n_items = cfg["n_users"], cfg["n_items"]
    counts = np.minimum(datagen.degree_sequence(
        n_users, int(round(cfg["events_per_user"] * n_users)),
        cfg["events_sigma"], rng), cfg["seen_limit"])
    offsets = np.concatenate([[0], np.cumsum(counts)])
    item = rng.integers(0, n_items, offsets[-1], dtype=np.int32)
    buy = rng.random(offsets[-1]) < cfg["buy_share"]
    withdrawn = []
    for j, user in enumerate(hot_users(cfg)):
        slots = rng.permutation(TOP)
        if j < cfg["withdrawn_hot_users"]:
            withdrawn.append(hot_top[j, slots[0]])
            slots = slots[1:]
        take = slots[:counts[user]]
        item[offsets[user]:offsets[user] + len(take)] = hot_top[j, take]
    withdrawn = np.unique(np.asarray(withdrawn, np.int64))
    while len(withdrawn) < cfg["unavailable_items"]:
        more = rng.integers(0, n_items, cfg["unavailable_items"]
                            - len(withdrawn))
        withdrawn = np.unique(np.concatenate([withdrawn, more]))
    return {"offsets": offsets, "item": item, "buy": buy,
            "withdrawn": withdrawn}


def seen_of(ev: dict, user: int) -> np.ndarray:
    return ev["item"][ev["offsets"][user]:ev["offsets"][user + 1]]


def store_rows(ev: dict, seed: int, now_us: int):
    """One row per event in the event table's column order (``id``, event,
    entity type and id, target type and id, properties, event time, tags,
    prId, creation time), user by user; times within the year before
    ``now_us``."""
    rng = np.random.default_rng([int(seed), EVENT_STREAM, 1])
    n = len(ev["item"])
    times = now_us - rng.integers(1, 365 * 86_400_000_000, n)
    users = np.repeat(np.arange(len(ev["offsets"]) - 1),
                      np.diff(ev["offsets"]))
    names = np.where(ev["buy"], "buy", "view")
    return ((f"{k:08x}", name, "user", str(user), "item", str(it), "{}",
             t, "[]", None, t)
            for k, (name, user, it, t) in enumerate(zip(
                names.tolist(), users.tolist(), ev["item"].tolist(),
                times.tolist())))


# -- which request carries which rule ----------------------------------------


def cycle_ranks(due) -> np.ndarray:
    """The rank of each row's inter-arrival gap among the mix's gaps, which
    ``loadgen.schedule`` draws as the quantiles of an exponential, orders by
    the mix's own seed and only TURNS by ``--seed``: a row's rank names its
    place in the mix's arrival cycle whatever the seed and whatever the
    rate. Row 0's gap is not in the schedule (``due`` starts at 0): it is
    the one rank no other row has."""
    due = np.asarray(due, np.float64)
    n = len(due)
    if n == 1:
        return np.zeros(1, np.int64)
    u = -np.log1p(-(np.arange(n) + 0.5) / n)
    mids = (u[1:] + u[:-1]) / 2
    gaps = np.diff(due)
    # the longest gap seen is the longest of all unless row 0 holds that one
    for longest in (u[-1], u[-2]):
        ranks = np.searchsorted(mids, gaps * (longest / gaps.max()))
        if len(np.unique(ranks)) == n - 1:
            missing = np.setdiff1d(np.arange(n), ranks)
            return np.concatenate([missing, ranks])
    raise ValueError("the schedule's gaps are not the quantiles of an "
                     "exponential: no place in the cycle can be read back")


def rules_of(traffic: dict, due) -> list[str]:
    """The rule of each row of a schedule: ``rule_shares`` laid over the
    gaps' ranks in an order drawn from the mix's ``arrival_seed``, so the
    same place of the arrival cycle carries the same rule in every seed."""
    n = len(due)
    shares = traffic["rule_shares"]
    counts = {rule: int(round(float(shares[rule]) * n)) for rule in RULES[1:]}
    by_rank = np.array(sum(([rule] * c for rule, c in counts.items()), [])
                       + ["none"] * (n - sum(counts.values())))
    mix = np.random.default_rng([int(traffic["arrival_seed"]), RULE_STREAM])
    return by_rank[mix.permutation(n)][cycle_ranks(due)].tolist()


def request_lists(cfg: dict, traffic: dict, seed: int, sched: dict,
                  cats: np.ndarray, hot_top: np.ndarray) -> list[dict]:
    """For each row of the schedule, the rule fields of its body as item
    rows and category indices: ``{}``, ``{"categories": [c]}`` (drawn by
    category size), ``{"blackList": ids}`` (1-20, from the user's own top
    where the user is hot) or ``{"whiteList": ids}`` (50-500 of one
    category: a campaign shelf). The ids are drawn from ``--seed``."""
    rng = np.random.default_rng([int(seed), BODY_STREAM])
    hot = {int(u): j for j, u in enumerate(hot_users(cfg))}
    shares = category_shares(cfg)
    lo_b, hi_b = traffic["black_list_len"]
    lo_w, hi_w = traffic["white_list_len"]
    members: dict[int, np.ndarray] = {}
    out = []
    for user, rule in zip(sched["user"], rules_of(traffic, sched["due"])):
        if rule == "none":
            out.append({})
        elif rule == "categories":
            out.append({"categories": [int(rng.choice(len(shares),
                                                      p=shares))]})
        elif rule == "blackList":
            ids = rng.integers(0, cfg["n_items"],
                               int(rng.integers(lo_b, hi_b + 1)))
            j = hot.get(int(user)) if user.isdigit() else None
            if j is not None:
                own = rng.permutation(hot_top[j])[:len(ids)]
                ids[:len(own)] = own
            out.append({"blackList": np.unique(ids)})
        else:
            c = int(rng.choice(len(shares), p=shares))
            if c not in members:
                members[c] = np.flatnonzero(cats == c)
            size = min(int(rng.integers(lo_w, hi_w + 1)), len(members[c]))
            out.append({"whiteList": rng.choice(members[c], size,
                                                replace=False)})
    return out
