"""The sparse latent read of ONE layer, timed ALONE on the chip at the shapes
of `glm53-flash-ep8_serve_repo_sessions` (PR 61):

    chiprun --chips 1 -- python3 tools/probe_sparse_read.py [--slots 64]
        [--live 25] [--lanes 2] [--position 33000] [--reps 10]

64 decode rows of which `--live` sit at ~`--position` positions (the others
idle on the null block), 64 heads over rows of 512 values, 32 index heads of
128 over the ~8,250 pooled keys of a row's table, the best 512 groups of 4 and
the tail. Timed, each as `INNER` calls chained inside ONE launch:

- `sparse_decode`: the whole op as the decode tick runs it (the pooled row's
  write, index scores, `top_k`, the gather into the scratch, the latent
  read's decode body over the scratch);
- `sparse_mixed`: the same with `--lanes` lanes of 128 rows beside them (the
  mixed tick's 320 rows);
- its parts at the decode shape alone: `index_scores`, `top_k`, `gather`,
  `attend` (the decode body over a ready scratch);
- `dense_decode`: `latent_paged_attention`'s decode body over the WHOLE table
  of the same rows (what the read would cost with the selection ignored:
  the candidate the selection has to beat at this length).

One JSON line of median milliseconds a call. What the numbers decided is in
PERF.md section 6, PR 61 (the plain gather route against a kernel of 512
four-row DMAs a row, which is not built)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INNER = 20


def _median_ms(fn, args, reps):
    """Median milliseconds of ONE call of `fn(carry, *args) -> carry` (a
    scalar the next call's inputs are nudged by, so that no call is elided),
    `INNER` calls inside one jitted loop; a part that fails says why and the
    others still report."""
    try:
        return _timed(fn, args, reps)
    except Exception as e:      # noqa: BLE001  (a probe: report and go on)
        return f"{type(e).__name__}: {str(e)[:300]}"


def _timed(fn, args, reps):
    import jax
    import jax.numpy as jnp
    loop = jax.jit(lambda *a: jax.lax.fori_loop(
        0, INNER, lambda _, c: fn(c, *a), jnp.zeros((), jnp.float32)))
    times = []
    for k in range(reps + 2):
        t = time.perf_counter()
        jax.block_until_ready(loop(*args))
        if k >= 2:
            times.append(1e3 * (time.perf_counter() - t) / INNER)
    return float(np.median(times))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--live", type=int, default=25)
    ap.add_argument("--lanes", type=int, default=2)
    ap.add_argument("--position", type=int, default=33000)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import latent_attention as la
    from paddle_tpu.fusion import sparse_latent_attention as sla

    S, L, C = args.slots, args.lanes, 128
    nb, bs, nlb, nh, c, ni, di, top, kp = 8192, 64, 552, 64, 512, 32, 128, \
        512, 4
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    pool = jax.random.normal(key, (nb, 1, bs, c), bf16)
    ipool = jax.random.normal(jax.random.fold_in(key, 1),
                              (nb, 1, bs // kp, di), bf16)
    table = jnp.asarray(rng.standard_normal((nlb * bs, 64)), f32)

    def rows(n_rows, n_live, lanes):
        pos = np.zeros((n_rows,), np.int64)
        btab = np.zeros((S, nlb), np.int64)
        wblock, woff = np.zeros((S,), np.int64), np.zeros((S,), np.int64)
        for s in range(n_live):
            p = args.position + 17 * s
            blocks = rng.choice(np.arange(1, nb), p // bs + 1, replace=False)
            btab[s, :len(blocks)] = blocks
            pos[s], wblock[s], woff[s] = p, blocks[-1], p % bs
        lane = ()
        if lanes:
            lbtab = np.zeros((lanes, nlb), np.int64)
            lwb = np.zeros((lanes * C // bs,), np.int64)
            for j in range(lanes):
                p0 = (args.position // C) * C
                blocks = rng.choice(np.arange(1, nb), p0 // bs + C // bs,
                                    replace=False)
                lbtab[j, :len(blocks)] = blocks
                lwb[j * 2:j * 2 + 2] = blocks[-2:]
                pos[S + j * C:S + (j + 1) * C] = p0 + np.arange(C)
            lane = (jnp.asarray(lbtab, i32), jnp.asarray(lwb, i32),
                    jnp.full((lanes,), C, i32))
        k = jax.random.fold_in(key, n_rows)
        return dict(
            q=jax.random.normal(k, (n_rows, 1, nh * c), bf16),
            qi=jax.random.normal(jax.random.fold_in(k, 1),
                                 (n_rows, ni * di), bf16),
            ki=jax.random.normal(jax.random.fold_in(k, 2), (n_rows, di), bf16),
            wi=jax.random.normal(jax.random.fold_in(k, 3), (n_rows, ni), f32),
            pos=jnp.asarray(pos, f32).reshape(n_rows, 1, 1),
            btab=jnp.asarray(btab, i32), wblock=jnp.asarray(wblock, i32),
            woff=jnp.asarray(woff, i32), lane=lane)

    kw = dict(num_heads=nh, v_width=c, scale=256 ** -0.5, index_heads=ni,
              top_groups=top, kpool=kp)

    def whole(r):
        def fn(carry, pool, ipool, table):
            out, _ = sla.sparse_latent_attention(
                r["q"] + carry.astype(bf16), pool, ipool,
                r["qi"] + carry.astype(bf16), r["ki"] + carry.astype(bf16),
                r["wi"], r["pos"], table, r["btab"], r["wblock"], r["woff"],
                (*r["lane"], C) if r["lane"] else None, **kw)
            return jnp.sum(out[0, 0, :4].astype(f32)) * 1e-9
        return fn

    dec, mix = rows(S, args.live, 0), rows(S + L * C, args.live, L)
    out = {"slots": S, "live": args.live, "lanes": L,
           "position": args.position,
           "device": jax.devices()[0].device_kind}
    out["sparse_decode_ms"] = _median_ms(whole(dec), (pool, ipool, table),
                                         args.reps)
    out["sparse_mixed_ms"] = _median_ms(whole(mix), (pool, ipool, table),
                                        args.reps)
    # the parts, at the decode shape
    gpb = bs // kp
    pos = dec["pos"].reshape(-1).astype(i32)
    qi = dec["qi"].reshape(S, 1, ni, di)
    wi = dec["wi"].reshape(S, 1, ni)

    def scores(carry, ipool):
        sc = sla.index_scores(qi + carry.astype(bf16), wi,
                              ipool[dec["btab"]].reshape(S, nlb * gpb, di))
        return jnp.sum(sc[0, 0, :4]) * 1e-9
    out["index_scores_ms"] = _median_ms(scores, (ipool,), args.reps)
    sc = jax.random.normal(key, (S, nlb * gpb), f32)

    def topk(carry, sc):
        _, idx = jax.lax.top_k(sc + carry, top)
        return jnp.sum(idx[0, :4]).astype(f32) * 1e-9
    out["top_k_ms"] = _median_ms(topk, (sc,), args.reps)
    t_rows = sla.scratch_rows(top, kp, bs, nlb)
    ids, count = sla.select(sc, pos, dec["btab"], kp, top, gpb, t_rows // kp)
    n_blk = t_rows // bs
    flat = jnp.concatenate([jnp.zeros((gpb,), i32), ids.reshape(-1)])

    def consumer(scratch):
        """The gathered rows as the attend kernel takes them (a pool of
        blocks of 64), read once."""
        blocks = scratch.reshape(1 + S * n_blk, 1, bs, c)
        return jnp.sum(blocks[:, 0, ::16, :8].astype(f32)) * 1e-9

    def gather(carry, pool):            # A: groups of a reshaped pool
        return consumer(
            pool.reshape(-1, kp, c)[flat + (carry > 1).astype(i32)])
    out["gather_ms"] = _median_ms(gather, (pool,), args.reps)

    def gather_windows(carry, pool):    # B: (4, 512) windows of the 2-D pool
        dn = jax.lax.GatherDimensionNumbers(
            offset_dims=(1, 2), collapsed_slice_dims=(),
            start_index_map=(0,))
        start = (flat * kp + (carry > 1).astype(i32))[:, None]
        return consumer(jax.lax.gather(
            pool.reshape(-1, c), start, dn, (kp, c), mode="clip"))
    out["gather_windows_ms"] = _median_ms(gather_windows, (pool,), args.reps)

    def gather_rows(carry, pool):       # C: single rows of the 2-D pool
        rows_ = (flat[:, None] * kp + jnp.arange(kp, dtype=i32)).reshape(-1)
        return consumer(pool.reshape(-1, c)[rows_ + (carry > 1).astype(i32)])
    out["gather_rows_ms"] = _median_ms(gather_rows, (pool,), args.reps)

    # D: a pool stored by groups, [NB * 16, 4, 512]: no copy a call
    def in_use():
        return (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
    before = in_use()
    native = jax.block_until_ready(
        jax.random.normal(key, (nb * gpb, kp, c), bf16))
    out["native_pool_gb"] = (in_use() - before) / 1e9

    def gather_native(carry, native):
        return consumer(native[flat + (carry > 1).astype(i32)])
    out["gather_native_ms"] = _median_ms(gather_native, (native,), args.reps)
    del native

    phys = (jnp.repeat(dec["btab"], gpb, axis=1) * gpb
            + jnp.tile(jnp.arange(gpb, dtype=i32), nlb)[None, :])

    def sort_payload(carry, sc):        # the ids ride through the sort
        _, ids_ = jax.lax.sort((-(sc + carry), phys), num_keys=1)
        return jnp.sum(ids_[0, :4]).astype(f32) * 1e-9
    out["sort_payload_ms"] = _median_ms(sort_payload, (sc,), args.reps)

    def map_ids(carry, sc):             # top_k, then the ids looked up
        _, idx = jax.lax.top_k(sc + carry, top)
        ids_ = jnp.take_along_axis(phys, idx, axis=1)
        return jnp.sum(ids_[0, :4]).astype(f32) * 1e-9
    out["top_k_and_lookup_ms"] = _median_ms(map_ids, (sc,), args.reps)
    scratch = pool.reshape(-1, kp, c)[flat].reshape(1 + S * n_blk, 1, bs, c)
    tab = 1 + jnp.arange(S * n_blk, dtype=i32).reshape(S, n_blk)
    live = (dec["wblock"] > 0).astype(i32)

    def attend(carry, scratch):
        o = la._latent_decode_pallas(
            dec["q"] + carry.astype(bf16), scratch, tab,
            jnp.maximum(count, 1) - 1, live, nh, c, 256 ** -0.5,
            interpret=False)
        return jnp.sum(o[0, 0, :4].astype(f32)) * 1e-9
    out["attend_ms"] = _median_ms(attend, (scratch,), args.reps)

    def dense(carry, pool):
        o = la.latent_paged_attention(dec["q"] + carry.astype(bf16), pool,
                                      dec["btab"], pos, nh, c, 256 ** -0.5,
                                      rows=live)
        return jnp.sum(o[0, 0, :4].astype(f32)) * 1e-9
    out["dense_decode_ms"] = _median_ms(dense, (pool,), args.reps)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
