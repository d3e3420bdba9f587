"""The sparse latent read of ONE layer, timed ALONE on the chip at the shapes
of `glm53-flash-ep8_serve_repo_sessions` (PR 61, PR 62, PR 64):

    chiprun --chips 1 -- python3 tools/probe_sparse_read.py [--slots 64]
        [--live 7,11] [--lane-rows 128:0,128:40,128:128] [--position 33000]
        [--rows 8,16,32,64,128,192,256,320] [--reps 6]
        [--parts whole,selection]   (and `read`: PR 62's forms)

64 decode rows of which `--live`, scattered over the slots, sit at
~`--position` positions (the others idle on the null block), 64 heads over
rows of 512 values, 32 index heads of 128 over the ~8,250 pooled keys of a
row's table, the best 512 groups of 4 and the tail. A mixed tick adds lanes
of 128 rows, each with the REAL rows `--lane-rows` gives it (`128:0`: one
whole chunk beside an idle lane; `128:40`: a whole chunk and a short last
one), once a shape. Every part is timed as the TICK runs it: `INNER` calls
chained inside ONE launch whose loop CARRIES the pools and writes a row of
them before each call, as the tick's own write does, so that nothing that
reads a pool can be hoisted out of the loop. Timed, for each count of
`--live`:

- `sparse_decode`: the whole op as the decode tick runs it (the pooled row's
  write, index scores, the sort, the picked rows' fetch and attend);
- `sparse_mixed_<a>_<b>`: the same with lanes of a and b real rows beside
  them (the mixed tick's 320 rows);
- the picked rows' way from the pool to the context, three forms, at the
  decode shape (`read_*_decode`) and at the mixed tick's 320 rows
  (`read_*_mixed`), each from the SAME `ids` and `count`:
  - `groups` (A, PR 61's): the pool regrouped `[NB*16, 4, 512]` (a copy of
    the pool), groups gathered, the scratch regrouped back to blocks of 64 (a
    copy of the scratch), the latent read's decode body over it;
  - `rows` (C / F): single rows gathered from the pool seen as `[NB*64, 512]`
    (a bitcast) straight into blocks of 64, the same decode body;
  - `fetch` (K): `_sparse_fetch_pallas`, one DMA a picked group's 8-row chunk
    from the pool in HBM, live rows only, no scratch;
  and `gather_groups` / `gather_rows`: A's and C's gathers alone, their
  scratch read once AS BLOCKS OF 64 (what the attend kernel takes);
- the selection's parts by ROWS (`--rows`; PR 64: is a row's cost flat down
  to 8 rows?): `index_scores_ms_rows<r>` (decode rows, with the index pool's
  gather through the table, r <= the slots), `sort_payload_ms_rows<r>` (the
  sort with the ids as its payload), and `sort_loop8_ms_rows<r>`: the same
  rows sorted 8 at a time in a loop (the form `over_live_rows` took: its
  trip count is the device's, so ONE sort program serves every live count);
- `dense_decode`: `latent_paged_attention`'s decode body over the WHOLE table
  of the same rows (the selection ignored).

`fetch_vs_rows_max_abs` holds K's context to C's on the chip. A form this
checkout does not have reports its error and the others still run, so the
file copied into a parent's checkout times the parent in the same call: the
parent unpacked into a git-ignored directory of the repo, this file copied
over its own, then `python3 .scratch/parent/tools/probe_sparse_read.py
--parts whole` and `python3 tools/probe_sparse_read.py` in ONE call.

One JSON line of median milliseconds a call. PR 61 took A on a probe whose
loop HOISTED A's copy of the pool (its pool was a constant of the loop) and
whose consumer never paid the scratch's way back to blocks: in the tick A
cost 1.86 ms more on every tick and 2.4 more on a mixed one (ledger, PR 61).
What PR 62 read here and which regime took which form is in PERF.md section
6, PR 62; what the selection costs by rows, and the whole op with the
selection run for the live rows alone against the parent's, section 6, PR
64. (B, `(4, 512)` windows of the 2-D pool, read 44.9 ms in PR 61 and D, a
pool stored by groups, was not built: neither is timed any more.)"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INNER = 20


def _median_ms(fn, state, args, reps):
    """(median milliseconds of ONE call of `fn(carry, state, *args) -> carry`
    (a scalar the next call's inputs are nudged by, so that no call is
    elided), the pools as the launches left them); a part that fails says why
    in place of the time, hands back None, and the others still report."""
    try:
        return _timed(fn, state, args, reps)
    except Exception as e:      # noqa: BLE001  (a probe: report and go on)
        return f"{type(e).__name__}: {str(e)[:300]}", None


def _timed(fn, state, args, reps):
    """`state`: the pools, carried through the loop and written (one row of
    block 1 + i) before call i, in place: the launch donates them and the
    caller goes on with the ones that come back."""
    import jax
    import jax.numpy as jnp

    def body(i, c):
        carry, state = c
        state = tuple(p.at[1 + i, 0, 3].set(carry.astype(p.dtype))
                      for p in state)
        out = fn(carry, state, *args)
        return (out[0], out[1]) if isinstance(out, tuple) else (out, state)

    loop = jax.jit(lambda state, *a: jax.lax.fori_loop(
        0, INNER, body, (jnp.zeros((), jnp.float32), state)),
        donate_argnums=0)
    times = []
    for k in range(reps + 2):
        t = time.perf_counter()
        _, state = jax.block_until_ready(loop(state, *args))
        if k >= 2:
            times.append(1e3 * (time.perf_counter() - t) / INNER)
    return float(np.median(times)), state


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--live", default="7,11")
    ap.add_argument("--lane-rows", default="128:0,128:40,128:128")
    ap.add_argument("--rows", default="8,16,32,64,128,192,256,320")
    ap.add_argument("--position", type=int, default=33000)
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--parts", default="whole,selection")
    args = ap.parse_args(argv)
    parts = args.parts.split(",")
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import latent_attention as la
    from paddle_tpu.fusion import sparse_latent_attention as sla

    S, C = args.slots, 128
    lane_rows = [tuple(int(v) for v in m.split(":"))
                 for m in args.lane_rows.split(",")]
    nb, bs, nlb, nh, c, ni, di, top, kp = 8192, 64, 552, 64, 512, 32, 128, \
        512, 4
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    pools = {"pool": jax.random.normal(key, (nb, 1, bs, c), bf16),
             "ipool": jax.random.normal(jax.random.fold_in(key, 1),
                                        (nb, 1, bs // kp, di), bf16)}
    table = jnp.asarray(rng.standard_normal((nlb * bs, 64)), f32)
    scale = 256 ** -0.5

    def timed(fn, names, fn_args):
        """Time `fn` with the pools `names` carried; keep the pools that come
        back (the launch donated the ones that went in)."""
        ms, back = _median_ms(fn, tuple(pools[k] for k in names), fn_args,
                              args.reps)
        if back is not None:
            pools.update(zip(names, back))
        elif any(pools[k].is_deleted() for k in names):
            for k, shape in (("pool", (nb, 1, bs, c)),
                             ("ipool", (nb, 1, bs // kp, di))):
                pools[k] = jax.random.normal(key, shape, bf16)
        return ms

    def rows(n_live, lanes=()):
        """`n_live` decode rows scattered over the S slots, and a lane for
        each count of real rows in `lanes` (0: an idle lane, all zeros as
        the pager leaves it)."""
        n_rows = S + len(lanes) * C
        pos = np.zeros((n_rows,), np.int64)
        btab = np.zeros((S, nlb), np.int64)
        wblock, woff = np.zeros((S,), np.int64), np.zeros((S,), np.int64)
        for i, s in enumerate(sorted(rng.choice(S, n_live, replace=False))):
            p = args.position + 17 * i
            blocks = rng.choice(np.arange(32, nb), p // bs + 1, replace=False)
            btab[s, :len(blocks)] = blocks
            pos[s], wblock[s], woff[s] = p, blocks[-1], p % bs
        lane = ()
        if lanes:
            lbtab = np.zeros((len(lanes), nlb), np.int64)
            lwb = np.zeros((len(lanes) * C // bs,), np.int64)
            for j, real in enumerate(lanes):
                if not real:
                    continue
                p0 = (args.position // C) * C
                blocks = rng.choice(np.arange(32, nb), p0 // bs + C // bs,
                                    replace=False)
                lbtab[j, :len(blocks)] = blocks
                lwb[j * (C // bs):(j + 1) * (C // bs)] = blocks[-(C // bs):]
                pos[S + j * C:S + (j + 1) * C] = p0 + np.arange(C)
            lane = (jnp.asarray(lbtab, i32), jnp.asarray(lwb, i32),
                    jnp.asarray(lanes, i32))
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

    kw = dict(num_heads=nh, v_width=c, scale=scale, index_heads=ni,
              top_groups=top, kpool=kp)

    def whole(carry, state, r):
        pool, ipool = state
        out, ipool = sla.sparse_latent_attention(
            r["q"] + carry.astype(bf16), pool, ipool,
            r["qi"] + carry.astype(bf16), r["ki"] + carry.astype(bf16),
            r["wi"], r["pos"], table, r["btab"], r["wblock"], r["woff"],
            (*r["lane"], C) if r["lane"] else None, **kw)
        return jnp.sum(out[0, 0, :4].astype(f32)) * 1e-9, (pool, ipool)

    gpb = bs // kp
    t_rows = sla.scratch_rows(top, kp, bs, nlb)
    n_blk = t_rows // bs

    def selection(r):
        """A row's `ids`, `count`, `live` from seeded scores, as the op makes
        them, and the scratch's table."""
        n = r["q"].shape[0]
        pos = r["pos"].reshape(-1).astype(i32)
        tab, live = r["btab"], r["wblock"] > 0
        if r["lane"]:
            tab = jnp.concatenate([tab, jnp.repeat(r["lane"][0], C, axis=0)])
            live = jnp.concatenate(
                [live, (jnp.arange(C)[None, :] < r["lane"][2][:, None])
                 .reshape(-1)])
        sc = jax.random.normal(jax.random.fold_in(key, 7 + n),
                               (n, nlb * gpb), f32)
        ids, count = sla.select(sc, pos, tab, kp, top, gpb, t_rows // kp)
        return dict(q=r["q"], ids=ids, count=jnp.maximum(count, 1),
                    live=live.astype(i32),
                    tab=1 + jnp.arange(n * n_blk, dtype=i32)
                    .reshape(n, n_blk))

    def attend(q, scratch, sel):
        return la._latent_decode_pallas(
            q, scratch, sel["tab"], sel["count"] - 1, sel["live"], nh, c,
            scale, interpret=False)

    def scratch_groups(pool, sel):      # A: groups of a regrouped pool
        n = sel["ids"].shape[0]
        flat = jnp.concatenate([jnp.zeros((gpb,), i32),
                                sel["ids"].reshape(-1)])
        return pool.reshape(-1, kp, c)[flat].reshape(1 + n * n_blk, 1, bs, c)

    def scratch_rows_(pool, sel):       # C: single rows of the 2-D pool
        n = sel["ids"].shape[0]
        at = jnp.concatenate([jnp.zeros((bs,), i32),
                              sla.picked_rows(sel["ids"].reshape(-1), kp)])
        return pool.reshape(-1, c)[at].reshape(1 + n * n_blk, 1, bs, c)

    def fetch(q, pool, sel):            # K: the fetch kernel, no scratch
        return sla._sparse_fetch_pallas(
            q, pool, sel["ids"], sel["count"], sel["live"], nh, c, scale, kp,
            sla.fetch_chunk(kp, bs), interpret=False)

    def first(o):
        return jnp.sum(o[0, 0, :4].astype(f32)) * 1e-9

    def read_groups(carry, state, sel):
        return first(attend(sel["q"] + carry.astype(bf16),
                            scratch_groups(state[0], sel), sel))

    def read_rows(carry, state, sel):
        return first(attend(sel["q"] + carry.astype(bf16),
                            scratch_rows_(state[0], sel), sel))

    def read_fetch(carry, state, sel):
        return first(fetch(sel["q"] + carry.astype(bf16), state[0], sel))

    def consumed(scratch):
        """The gathered rows as the attend kernel takes them (a pool of
        blocks of 64), read once."""
        return jnp.sum(scratch[:, 0, ::16, :8].astype(f32)) * 1e-9

    def gather_groups(carry, state, sel):
        return consumed(scratch_groups(state[0], sel)) + carry * 1e-9

    def gather_rows(carry, state, sel):
        return consumed(scratch_rows_(state[0], sel)) + carry * 1e-9

    out = {"slots": S, "lane_rows": args.lane_rows,
           "position": args.position,
           "device": jax.devices()[0].device_kind}
    for n_live in (int(v) for v in args.live.split(",")):
        dec = rows(n_live)
        tag = f"_live{n_live}"
        if "whole" in parts:
            out["sparse_decode_ms" + tag] = timed(whole, ("pool", "ipool"),
                                                  (dec,))
            for lanes in lane_rows:
                name = "sparse_mixed_" + "_".join(str(v) for v in lanes)
                out[name + "_ms" + tag] = timed(
                    whole, ("pool", "ipool"), (rows(n_live, lanes),))
        for shape, r in (("decode", dec), ("mixed", rows(n_live,
                                                         lane_rows[-1]))):
            if "read" not in parts:
                break
            sel = selection(r)
            for name, fn in (("read_groups", read_groups),
                             ("read_rows", read_rows),
                             ("read_fetch", read_fetch),
                             ("gather_groups", gather_groups),
                             ("gather_rows", gather_rows)):
                out[f"{name}_{shape}_ms{tag}"] = timed(fn, ("pool",), (sel,))
            try:
                a = fetch(sel["q"], pools["pool"], sel)
                b = attend(sel["q"], scratch_rows_(pools["pool"], sel), sel)
                out[f"fetch_vs_rows_max_abs_{shape}{tag}"] = float(
                    jnp.max(jnp.abs(a.astype(f32) - b.astype(f32))))
            except Exception as e:      # noqa: BLE001
                out[f"fetch_vs_rows_max_abs_{shape}{tag}"] = \
                    f"{type(e).__name__}: {str(e)[:200]}"
        print(json.dumps(out), flush=True)
    if "selection" not in parts:
        return 0
    # the selection's parts by rows and the dense read, every slot live
    dec = rows(S)
    pos = dec["pos"].reshape(-1).astype(i32)
    n_rows = [int(v) for v in args.rows.split(",")]
    for r in (r for r in n_rows if r <= S):
        qi = dec["qi"].reshape(S, 1, ni, di)[:r]
        wi, tab = dec["wi"].reshape(S, 1, ni)[:r], dec["btab"][:r]

        def scores(carry, state, qi=qi, wi=wi, tab=tab):
            sc = sla.index_scores(
                qi + carry.astype(bf16), wi,
                state[0][tab].reshape(tab.shape[0], nlb * gpb, di),
                head_block=ni)
            return jnp.sum(sc[0, 0, :4]) * 1e-9
        out[f"index_scores_ms_rows{r}"] = timed(scores, ("ipool",), ())
    top_rows = max(n_rows)
    sc = jax.random.normal(key, (top_rows, nlb * gpb), f32)
    phys = jnp.tile(jnp.repeat(dec["btab"], gpb, axis=1) * gpb
                    + jnp.tile(jnp.arange(gpb, dtype=i32), nlb)[None, :],
                    (-(-top_rows // S), 1))

    def sort_payload(carry, state, sc, phys):   # the ids ride through the sort
        _, ids_ = jax.lax.sort((-(sc + carry), phys), num_keys=1)
        return jnp.sum(ids_[0, :4]).astype(f32) * 1e-9

    def sort_loop8(carry, state, sc, phys):     # the same rows, 8 at a time
        def eight(i, ids):
            at = (i * 8, 0)
            _, part = jax.lax.sort(
                (-(jax.lax.dynamic_slice(sc, at, (8, sc.shape[1])) + carry),
                 jax.lax.dynamic_slice(phys, at, (8, sc.shape[1]))),
                num_keys=1)
            return jax.lax.dynamic_update_slice(ids, part[:, :top], at)
        ids_ = jax.lax.fori_loop(0, sc.shape[0] // 8, eight,
                                 jnp.zeros((sc.shape[0], top), i32))
        return jnp.sum(ids_[0, :4]).astype(f32) * 1e-9
    for r in n_rows:
        out[f"sort_payload_ms_rows{r}"] = timed(sort_payload, (),
                                                (sc[:r], phys[:r]))
        out[f"sort_loop8_ms_rows{r}"] = timed(sort_loop8, (),
                                              (sc[:r], phys[:r]))
    live = (dec["wblock"] > 0).astype(i32)

    def dense(carry, state):
        o = la.latent_paged_attention(dec["q"] + carry.astype(bf16), state[0],
                                      dec["btab"], pos, nh, c, scale,
                                      rows=live)
        return jnp.sum(o[0, 0, :4].astype(f32)) * 1e-9
    out["dense_decode_ms"] = timed(dense, ("pool",), ())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
