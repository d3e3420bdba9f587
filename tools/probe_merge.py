"""Micro-probe: strategies for coalescing duplicate sparse-grad rows on TPU.

160k int32 ids in [0, 1M) with [160k, 128] f32 values (the DeepFM config's
merged-grad shape). Compares:
  a) unique + dup-index scatter-add  (current _merge_sparse_rows)
  b) argsort + run-boundary segment ids + SORTED scatter-add
  c) argsort + cumsum-diff (no scatter at all: gathers only)

    python tools/probe_merge.py
"""
import json
import time

import numpy as np


def main(n=159744, vocab=1000000, width=128):
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, vocab, (n,)).astype(np.int32))
    vals = jnp.asarray(rng.rand(n, width).astype(np.float32))

    def merge_unique(ids, vals):
        rows_u, inv = jnp.unique(ids, return_inverse=True, size=n,
                                 fill_value=vocab)
        out = jnp.zeros((n, width), jnp.float32).at[inv.reshape(-1)].add(vals)
        return rows_u, out

    def merge_sorted_scatter(ids, vals):
        perm = jnp.argsort(ids)
        sid = ids[perm]
        sval = vals.at[perm].get(unique_indices=True)
        new = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
        seg = jnp.cumsum(new) - 1                      # sorted, dup
        out = jnp.zeros((n, width), jnp.float32).at[seg].add(
            sval, indices_are_sorted=True)
        rows_u = jnp.full((n,), vocab, jnp.int32).at[seg].set(
            sid, indices_are_sorted=True)
        return rows_u, out

    def merge_cumsum(ids, vals):
        perm = jnp.argsort(ids)
        sid = ids[perm]
        sval = vals.at[perm].get(unique_indices=True)
        csum = jnp.cumsum(sval, axis=0)
        last = jnp.concatenate([sid[1:] != sid[:-1],
                                jnp.ones((1,), bool)])   # run ends
        new = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
        seg = jnp.cumsum(new) - 1
        # position of each run's END in sorted order, compacted to the front
        end_pos = jnp.full((n,), n - 1, jnp.int32).at[
            jnp.where(last, seg, n - 1)].max(jnp.arange(n, dtype=jnp.int32))
        runs = csum.at[end_pos].get(indices_are_sorted=True)
        prev = jnp.where((jnp.arange(n) == 0)[:, None], 0.0,
                         csum.at[jnp.clip(end_pos - 1, 0, n - 1)].get())
        # prev run's end cumsum: for run u>0 it's csum[end_pos[u-1]]
        prev_end = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                                    end_pos[:-1]])
        prevc = jnp.where((prev_end < 0)[:, None], 0.0,
                          csum.at[jnp.clip(prev_end, 0, n - 1)].get())
        out = runs - prevc
        rows_u = jnp.full((n,), vocab, jnp.int32).at[seg].set(
            sid, indices_are_sorted=True)
        return rows_u, out

    def merge_segscan(ids, vals):
        """Segmented inclusive scan over SORTED rows (Hillis-Steele shift
        adds) — no scatter anywhere, so nothing serializes per-index."""
        perm = jnp.argsort(ids)
        sid = ids[perm]
        sval = vals.at[perm].get(unique_indices=True)
        flag = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
        acc = sval
        f = flag
        off = 1
        while off < n:
            sh_acc = jnp.concatenate([jnp.zeros((off, width), acc.dtype),
                                      acc[:-off]])
            sh_f = jnp.concatenate([jnp.ones((off,), bool), f[:-off]])
            acc = jnp.where(f[:, None], acc, acc + sh_acc)
            f = f | sh_f
            off *= 2
        last = jnp.concatenate([sid[1:] != sid[:-1],
                                jnp.ones((1,), bool)])
        end_pos, = jnp.nonzero(last, size=n, fill_value=n - 1)
        nu = jnp.sum(last)
        valid = jnp.arange(n) < nu
        rows_u = jnp.where(valid, sid[end_pos],
                           vocab + jnp.arange(n, dtype=sid.dtype))
        vals_u = acc.at[end_pos].get(indices_are_sorted=True)
        return rows_u, vals_u

    def argsort_only(ids, vals):
        perm = jnp.argsort(ids)
        return ids[perm], vals.at[perm].get(unique_indices=True)

    def unique_only(ids, vals):
        rows_u, inv = jnp.unique(ids, return_inverse=True, size=n,
                                 fill_value=vocab)
        return rows_u, vals

    ref_r, ref_v = jax.jit(merge_unique)(ids, vals)
    for name, fn in (("unique_scatter", merge_unique),
                     ("sorted_scatter", merge_sorted_scatter),
                     ("cumsum_diff", merge_cumsum),
                     ("segscan", merge_segscan),
                     ("argsort_only", argsort_only),
                     ("unique_only", unique_only)):
        f = jax.jit(fn)
        try:
            r, v = f(ids, vals)
            float(jnp.asarray(v).ravel()[0])
        except Exception as e:
            print(json.dumps({"name": name, "err": f"{e!s:.100}"}),
                  flush=True)
            continue
        # correctness vs reference (compare sum over all rows + spot rows)
        ok = bool(jnp.allclose(jnp.sort(jnp.asarray(r)),
                               jnp.sort(jnp.asarray(ref_r))))
        okv = bool(jnp.allclose(v.sum(), ref_v.sum(), rtol=1e-4))
        best = None
        for _ in range(3):
            t0 = time.time()
            for _ in range(10):
                r, v = f(ids, vals)
            float(jnp.asarray(v).ravel()[0])
            dt = (time.time() - t0) / 10
            best = dt if best is None else min(best, dt)
        print(json.dumps({"name": name, "ms": round(best * 1e3, 2),
                          "rows_ok": ok, "vals_ok": okv}), flush=True)


if __name__ == "__main__":
    main()
