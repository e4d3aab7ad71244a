"""Ablation profile of the ALS half-step at bench shapes (VERDICT r2 #1).

Decomposes the steady-state half-step cost on the real device by timing
jitted variants that add one pipeline stage at a time:

  gather        y[cols] factor-row gather alone (the HBM random-read)
  + gram        per-tile normal-equation einsums (the useful MXU math)
  + onehot      the chunked scan's tile->row one-hot MXU reduction +
                windowed scatter-add (the suspected overhead)
  solve         the Pallas batched SPD solve at [rows, k, k]
  bucketed      the PROPOSED layout: rows bucketed by padded nnz
                (power-of-2 lengths), per-row grams directly from the
                einsum -- no tile reduction at all

Each variant runs inside one jit with an n-rep fori_loop whose carry
perturbs the factor matrix (defeats loop-invariant hoisting); the timed
number is steady-state per-rep after a warm-up dispatch, with a scalar
readback as the completion barrier (same protocol as bench.py).

Run: python tools/profile_als.py            (ml20m user+item sides)
     PIO_PROFILE_SCALE=ml1m python tools/profile_als.py

Results taken 2026-07 are summarized in docs/tpu.md ("Findings carried
from 2026-07"); none has been re-measured on the current machine.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import SCALES, synth_ratings  # noqa: E402


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def time_jit(fn, args, reps):
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    out = compiled(*args)
    _ = jax.device_get(jax.tree.leaves(out)[0].ravel()[:1])  # warm barrier
    t0 = time.perf_counter()
    out = compiled(*args)
    _ = jax.device_get(jax.tree.leaves(out)[0].ravel()[:1])
    dt = time.perf_counter() - t0
    return dt / reps


def build_chunked(col, val, lrow, chunk):
    n_tiles = col.shape[0]
    n_chunks = (n_tiles + chunk - 1) // chunk
    pad = n_chunks * chunk - n_tiles
    if pad:
        col = np.pad(col, ((0, pad), (0, 0)))
        val = np.pad(val, ((0, pad), (0, 0)))
        lrow = np.pad(lrow, (0, pad))
    col_c = col.reshape(n_chunks, chunk, -1)
    val_c = val.reshape(n_chunks, chunk, -1)
    lrow_c = lrow.reshape(n_chunks, chunk)
    span = int(np.maximum(lrow_c.max(1) - lrow_c[:, 0], 0).max()) + 1
    span = -(-span // 128) * 128
    return col_c, val_c, lrow_c, span


def build_tiled(row, col, val, n_rows, L, pad_col):
    """Vendored copy of the r2 tiled layout (ops/blocked.py, removed in
    r3) so this tool keeps reproducing the tile-scan measurements the
    2026-07 roofline (docs/tpu.md) cites. Returns (col [B, L], val [B, L],
    block_row [B])."""
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int32)
    val = np.asarray(val, np.float32)
    order = np.argsort(row, kind="stable")
    row_s, col_s, val_s = row[order], col[order], val[order]
    counts = np.bincount(row_s, minlength=n_rows).astype(np.int64)
    blocks_per_row = (counts + L - 1) // L
    n_blocks = max(int(blocks_per_row.sum()), 1)
    row_start = np.zeros(n_rows + 1, np.int64)
    np.cumsum(counts, out=row_start[1:])
    pos = np.arange(len(row_s), dtype=np.int64) - row_start[row_s]
    block_off = np.zeros(n_rows + 1, np.int64)
    np.cumsum(blocks_per_row, out=block_off[1:])
    flat = (block_off[row_s] + pos // L) * L + pos % L
    col_b = np.full(n_blocks * L, pad_col, np.int32)
    val_b = np.zeros(n_blocks * L, np.float32)
    col_b[flat] = col_s
    val_b[flat] = val_s
    block_row = np.repeat(np.arange(n_rows, dtype=np.int64),
                          blocks_per_row).astype(np.int32)
    if block_row.shape[0] == 0:
        block_row = np.zeros(1, np.int32)
    return col_b.reshape(n_blocks, L), val_b.reshape(n_blocks, L), block_row


def profile_side(name, rows, cols, vals, n_rows, n_cols, k, reps):
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.pallas_kernels import batched_spd_solve

    L = 32
    chunk = 2048
    t_col, t_val, t_brow = build_tiled(rows, cols, vals, n_rows, L,
                                       pad_col=n_cols)
    col_c, val_c, lrow_c, span = build_chunked(
        t_col, t_val, t_brow.astype(np.int32), chunk)
    n_tiles = t_col.shape[0]
    log(f"[{name}] tiles={n_tiles} chunks={col_c.shape[0]} span={span} "
        f"rows={n_rows} counterpart_rows={n_cols}")

    rng = np.random.default_rng(0)
    y = (rng.standard_normal((n_cols + 1, k)) / np.sqrt(k)).astype(np.float32)
    y[-1] = 0.0
    y_d, col_d, val_d, lrow_d = jax.device_put((y, col_c, val_c, lrow_c))
    cd = jnp.bfloat16

    def perturb(y, i):
        # Tie the table to the rep index so XLA cannot hoist the loop body.
        return (y + i.astype(jnp.float32) * 1e-6).astype(cd)

    # --- gather only ------------------------------------------------------
    def gather_only(y, col_c):
        def rep(i, acc):
            y_cd = perturb(y, i)

            def body(c, chunk_cols):
                return c + jnp.take(y_cd, chunk_cols, axis=0).sum(
                    dtype=jnp.float32), None

            s, _ = jax.lax.scan(body, jnp.float32(0), col_c)
            return acc + s

        return jax.lax.fori_loop(0, reps, rep, jnp.float32(0))

    t_gather = time_jit(gather_only, (y_d, col_d), reps)

    # --- gather + gram ----------------------------------------------------
    def gather_gram(y, col_c, val_c):
        def rep(i, acc):
            y_cd = perturb(y, i)

            def body(c, chunk):
                ccol, cval = chunk
                p = jnp.take(y_cd, ccol, axis=0)
                grams = jnp.einsum("blk,blm->bkm", p, p,
                                   preferred_element_type=jnp.float32)
                rhs = jnp.einsum("blk,bl->bk", p, cval.astype(cd),
                                 preferred_element_type=jnp.float32)
                return c + grams.sum() + rhs.sum(), None

            s, _ = jax.lax.scan(body, jnp.float32(0), (col_c, val_c))
            return acc + s

        return jax.lax.fori_loop(0, reps, rep, jnp.float32(0))

    t_gram = time_jit(gather_gram, (y_d, col_d, val_d), reps)

    # --- full chunked scan: gather + gram + one-hot + window add ----------
    span_iota = jnp.arange(span, dtype=jnp.int32)
    rows_pad = n_rows + span

    def full_scan(y, col_c, val_c, lrow_c):
        def rep(i, carry):
            a0, b0 = carry
            y_cd = perturb(y, i)

            def body(c, chunk):
                a_acc, b_acc = c
                ccol, cval, clrow = chunk
                p = jnp.take(y_cd, ccol, axis=0)
                grams = jnp.einsum("blk,blm->bkm", p, p,
                                   preferred_element_type=jnp.float32)
                rhs = jnp.einsum("blk,bl->bk", p, cval.astype(cd),
                                 preferred_element_type=jnp.float32)
                rbase = clrow[0]
                local = clrow - rbase
                onehot = (local[None, :] == span_iota[:, None]).astype(cd)
                part_a = jnp.einsum("rc,ckm->rkm", onehot, grams.astype(cd),
                                    preferred_element_type=jnp.float32)
                part_b = jnp.einsum("rc,ck->rk", onehot, rhs.astype(cd),
                                    preferred_element_type=jnp.float32)
                a_win = jax.lax.dynamic_slice(a_acc, (rbase, 0, 0), (span, k, k))
                b_win = jax.lax.dynamic_slice(b_acc, (rbase, 0), (span, k))
                a_acc = jax.lax.dynamic_update_slice(a_acc, a_win + part_a,
                                                     (rbase, 0, 0))
                b_acc = jax.lax.dynamic_update_slice(b_acc, b_win + part_b,
                                                     (rbase, 0))
                return (a_acc, b_acc), None

            (a, b), _ = jax.lax.scan(body, (a0, b0), (col_c, val_c, lrow_c))
            return (a, b)

        a0 = jnp.zeros((rows_pad, k, k), jnp.float32)
        b0 = jnp.zeros((rows_pad, k), jnp.float32)
        return jax.lax.fori_loop(0, reps, rep, (a0, b0))

    t_full = time_jit(full_scan, (y_d, col_d, val_d, lrow_d), reps)

    # --- solve alone ------------------------------------------------------
    a_host = (rng.standard_normal((n_rows, k, k)) * 0.1).astype(np.float32)
    a_host = a_host @ a_host.transpose(0, 2, 1) + 3.0 * np.eye(k, dtype=np.float32)
    b_host = rng.standard_normal((n_rows, k)).astype(np.float32)
    a_d, b_d = jax.device_put((a_host, b_host))
    platform = jax.devices()[0].platform

    def solve(a, b):
        def rep(i, acc):
            x = batched_spd_solve(a + i * 1e-6, b, platform=platform)
            return acc + x.sum()

        return jax.lax.fori_loop(0, reps, rep, jnp.float32(0))

    t_solve = time_jit(solve, (a_d, b_d), reps)

    # --- PROPOSED: bucketed per-row grams ---------------------------------
    counts = np.bincount(np.asarray(rows, np.int64), minlength=n_rows)
    pad_len = np.maximum(L, 2 ** np.ceil(np.log2(np.maximum(counts, 1))
                                         ).astype(np.int64))
    order = np.argsort(rows, kind="stable")
    rs, cs, vs = np.asarray(rows)[order], np.asarray(cols)[order], np.asarray(vals)[order]
    row_start = np.zeros(n_rows + 1, np.int64)
    np.cumsum(counts, out=row_start[1:])
    pos = np.arange(len(rs)) - row_start[rs]

    buckets = []
    total_padded = 0
    for Lb in np.unique(pad_len):
        rows_b = np.where(pad_len == Lb)[0]
        if not rows_b.size:
            continue
        slot = np.full(n_rows, -1, np.int64)
        slot[rows_b] = np.arange(rows_b.size)
        in_b = slot[rs] >= 0
        colb = np.full((rows_b.size, Lb), n_cols, np.int32)
        valb = np.zeros((rows_b.size, Lb), np.float32)
        colb[slot[rs[in_b]], pos[in_b]] = cs[in_b]
        valb[slot[rs[in_b]], pos[in_b]] = vs[in_b]
        buckets.append((int(Lb), jax.device_put(colb), jax.device_put(valb)))
        total_padded += rows_b.size * int(Lb)
    log(f"[{name}] buckets={[(Lb, c.shape[0]) for Lb, c, _ in buckets]} "
        f"padded_nnz={total_padded} (x{total_padded/len(rs):.2f} of nnz)")

    # Row-chunk large buckets so the gathered [R, Lb, k] stays < ~256 MB.
    ENTRY_BUDGET = 64 * 1024 * 1024 // (2 * k)

    def bucketed(y, *flat):
        it = iter(flat)
        bucket_args = [(Lb, next(it), next(it)) for Lb, _, _ in buckets]

        def rep(i, acc):
            y_cd = perturb(y, i)
            total = jnp.float32(0)
            for Lb, colb, valb in bucket_args:
                R = colb.shape[0]
                rows_chunk = max(1, min(R, ENTRY_BUDGET // Lb))
                n_sub = -(-R // rows_chunk)
                padR = n_sub * rows_chunk - R
                cc = jnp.pad(colb, ((0, padR), (0, 0)),
                             constant_values=n_cols)
                vv = jnp.pad(valb, ((0, padR), (0, 0)))
                cc = cc.reshape(n_sub, rows_chunk, Lb)
                vv = vv.reshape(n_sub, rows_chunk, Lb)

                def body(c, chunk):
                    ccol, cval = chunk
                    p = jnp.take(y_cd, ccol, axis=0)
                    grams = jnp.einsum("rlk,rlm->rkm", p, p,
                                       preferred_element_type=jnp.float32)
                    rhs = jnp.einsum("rlk,rl->rk", p, cval.astype(cd),
                                     preferred_element_type=jnp.float32)
                    return c + grams.sum() + rhs.sum(), None

                s, _ = jax.lax.scan(body, jnp.float32(0), (cc, vv))
                total = total + s
            return acc + total

        return jax.lax.fori_loop(0, reps, rep, jnp.float32(0))

    flat = [x for _, c, v in buckets for x in (c, v)]
    t_bucketed = time_jit(bucketed, (y_d, *flat), reps)

    gf_gram = 2 * 2 * n_tiles * L * k * k / 1e9  # grams+rhs ~ 2x entries*k^2
    gf_onehot = 2 * 2 * col_c.shape[0] * span * chunk * k * k / 1e9
    log(f"[{name}] per half-step: gather {t_gather*1e3:7.1f} ms | "
        f"+gram {t_gram*1e3:7.1f} ms | full-scan {t_full*1e3:7.1f} ms | "
        f"solve {t_solve*1e3:7.1f} ms")
    log(f"[{name}] bucketed(gather+per-row gram) {t_bucketed*1e3:7.1f} ms")
    log(f"[{name}] implied: onehot+windowing = {max(t_full-t_gram,0)*1e3:.1f} ms "
        f"({max(t_full - t_gram, 0) / max(t_full, 1e-9) * 100:.0f}% of scan); "
        f"gram FLOPs {gf_gram:.0f} GF vs onehot {gf_onehot:.0f} GF")
    return {
        "gather_ms": t_gather * 1e3, "gather_gram_ms": t_gram * 1e3,
        "full_scan_ms": t_full * 1e3, "solve_ms": t_solve * 1e3,
        "bucketed_ms": t_bucketed * 1e3,
    }


def main():
    scale = os.environ.get("PIO_PROFILE_SCALE", "ml20m")
    k = int(os.environ.get("PIO_PROFILE_RANK", "32"))
    reps = int(os.environ.get("PIO_PROFILE_REPS", "5"))
    n_users, n_items, nnz = SCALES[scale]
    import jax

    log(f"[profile] scale={scale} rank={k} reps={reps} devices={jax.devices()}")
    u, i, r = synth_ratings(n_users, n_items, nnz)
    res_u = profile_side("user-side", u, i, r, n_users, n_items, k, reps)
    res_i = profile_side("item-side", i, u, r, n_items, n_users, k, reps)
    full = res_u["full_scan_ms"] + res_u["solve_ms"] + res_i["full_scan_ms"] + res_i["solve_ms"]
    prop = res_u["bucketed_ms"] + res_u["solve_ms"] + res_i["bucketed_ms"] + res_i["solve_ms"]
    log(f"[profile] current iteration ≈ {full:.1f} ms; bucketed ≈ {prop:.1f} ms "
        f"(projected {full/max(prop,1e-9):.1f}x)")


if __name__ == "__main__":
    main()
