"""Standalone microbench: Pallas partition kernel vs the 13-lane
``lax.sort`` it replaces, plus the end-to-end A/B at the bench workload.

Usage:
  python profiling/profile_partition.py kernel [ROWS] [REPS]
      Time ONE full-array stable re-compaction both ways on synthetic
      wave-shaped windows (default 1M rows; run 10500000 for the
      reference scale).  Prints ms per pass for: lax.sort on the key
      lane + payload, and dest-computation + apply_partition.
  python profiling/profile_partition.py e2e [ROWS] [ITERS]
      Steady-state iters/sec of the bench workload with
      tpu_wave_pallas_partition / tpu_wave_pallas_scan off vs auto —
      the per-leg delta.

Run ALONE on the chip (one process per chip); timing syncs by fetching a
scalar of the result, which waits for it.
"""

import gc
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(x):
    return float(np.asarray(x.reshape(-1)[0]))


def bench_kernel(rows: int, reps: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbm_tpu.ops.histogram import _on_tpu
    from lightgbm_tpu.ops.partition_pallas import (apply_partition,
                                                   exclusive_cumsum_i32)

    fw = 7                                    # 28 features packed
    n = rows
    rng = np.random.RandomState(0)
    bins = rng.randint(-2**31, 2**31 - 1, size=(fw, n)) \
        .astype(np.int64).astype(np.int32)
    w_p = rng.randn(3, n).astype(np.float32)
    rid = np.arange(n, dtype=np.int32)
    lid = rng.randint(0, 500, size=n).astype(np.int32)
    # wave-shaped windows: 4 disjoint split windows covering ~60% of rows
    w_slots = 64
    ps = np.zeros(w_slots, np.int32)
    cw = np.zeros(w_slots, np.int32)
    active = np.zeros(w_slots, bool)
    qs = [(0, int(0.25 * n)), (int(0.3 * n), int(0.15 * n)),
          (int(0.5 * n), int(0.1 * n)), (int(0.7 * n), int(0.1 * n))]
    go = rng.rand(n) < 0.47
    gl = np.zeros(n, bool)
    gr = np.zeros(n, bool)
    lc = np.zeros(w_slots, np.int32)
    for i, (s, c) in enumerate(qs):
        ps[i], cw[i], active[i] = s, c, True
        gl[s:s + c] = go[s:s + c]
        gr[s:s + c] = ~go[s:s + c]
        lc[i] = gl[s:s + c].sum()
    keys = np.zeros(n, np.int32)
    for i, (s, c) in enumerate(qs):
        keys[s:s + c] = np.where(gl[s:s + c], 2 * s, 2 * (s + lc[i]))
    pos_key = 2 * np.arange(n, dtype=np.int32)
    keys = np.where(gl | gr, keys, pos_key)

    j_bins = jnp.asarray(bins)
    j_w = jnp.asarray(w_p)
    j_rid = jnp.asarray(rid)
    j_lid = jnp.asarray(lid)
    j_keys = jnp.asarray(keys)

    @jax.jit
    def do_sort(k, b, w, r, l):
        ops = [k] + [b[i] for i in range(fw)] + [w[0], w[1], w[2], r, l]
        sd = lax.sort(ops, num_keys=1, is_stable=True)
        return sd[1]

    # per-member destination bases come from the decide-pass mask matmul
    # in the real program; here a per-row member-id gather stands in, so
    # the timing covers the two cumsums, dest selects and the kernel
    @jax.jit
    def do_partition(b, w, r, l, gl_a, gr_a, mem_of):
        cum = exclusive_cumsum_i32(jnp.stack([gl_a, gr_a]))
        cl, cr = cum[0], cum[1]
        pos = jnp.arange(n, dtype=jnp.int32)
        psj = jnp.asarray(ps)
        lcj = jnp.asarray(lc)
        bl = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              psj - cl[psj]])[mem_of]
        br = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              psj + lcj - cr[psj]])[mem_of]
        dest = jnp.where(gl_a > 0, bl + cl,
                         jnp.where(gr_a > 0, br + cr, pos))
        return apply_partition(
            b, w, r, l, dest, (gl_a | gr_a).astype(jnp.int32),
            psj, lcj, jnp.asarray(cw), jnp.asarray(active), cl, cr,
            cl[psj], cr[psj], interpret=not _on_tpu())[2]

    j_gl = jnp.asarray(gl.astype(np.int32))
    j_gr = jnp.asarray(gr.astype(np.int32))
    # member-of-row + 1 (0 = outside every window) for the base gather
    mem_row = np.zeros(n, np.int32)
    for i, (s, c) in enumerate(qs):
        mem_row[s:s + c] = i + 1
    j_mem = jnp.asarray(mem_row)

    out = do_sort(j_keys, j_bins, j_w, j_rid, j_lid)
    _sync(out)
    t0 = time.time()
    for _ in range(reps):
        out = do_sort(j_keys, j_bins, j_w, j_rid, j_lid)
    _sync(out)
    t_sort = (time.time() - t0) / reps * 1e3

    out = do_partition(j_bins, j_w, j_rid, j_lid, j_gl, j_gr, j_mem)
    _sync(out)
    t0 = time.time()
    for _ in range(reps):
        out = do_partition(j_bins, j_w, j_rid, j_lid, j_gl, j_gr, j_mem)
    _sync(out)
    t_part = (time.time() - t0) / reps * 1e3
    print(f"rows={n}  lax.sort={t_sort:.2f} ms  "
          f"partition={t_part:.2f} ms  speedup={t_sort / t_part:.2f}x")


def bench_e2e(rows: int, iters: int):
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    f = 28
    X = rng.randn(rows, f).astype(np.float64)
    logit = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] * 0.5 + np.sin(X[:, 3])
             + 0.5 * rng.randn(rows))
    y = (logit > 0).astype(np.float64)
    for mode in ("off", "auto"):
        params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
                  "learning_rate": 0.1, "min_data_in_leaf": 20,
                  "verbosity": -1, "metric": "none",
                  "tpu_wave_pallas_partition": mode,
                  "tpu_wave_pallas_scan": mode}
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.Booster(params, ds)
        sync = lambda: float(np.asarray(bst.gbdt.train_score.score[0, 0]))
        for _ in range(2):
            bst.update()
        sync()
        t0 = time.time()
        for _ in range(iters):
            bst.update()
        sync()
        dt = time.time() - t0
        print(f"pallas_partition/scan={mode}: {iters / dt:.3f} iters/s "
              f"({dt / iters * 1e3:.1f} ms/iter)")
        del bst, ds
        gc.collect()


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "kernel"
    rows = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
    reps = int(sys.argv[3]) if len(sys.argv) > 3 else 10
    if mode == "kernel":
        bench_kernel(rows, reps)
    else:
        bench_e2e(rows, reps)
