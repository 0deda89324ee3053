"""Standalone microbench: quantized-gradient training primitives
(`ops/quant.py`) vs their f32 counterparts, plus the end-to-end A/B at
the bench workload.

Usage:
  python profiling/profile_quant.py hist [ROWS] [REPS]
      Time ONE root-histogram build three ways on a bench-shaped
      problem (28 features, 255 bins): the f32 3-lane onehot
      contraction, the quantized 2-lane contraction (+ count-channel
      synthesis — the serial CPU quant path), and the packed int32
      single-pass accumulator (chunked; the XLA analogue of the
      reference OpenCL packed local accumulation).
  python profiling/profile_quant.py fused [ROWS] [REPS]
      Trace-level fused-vs-unfused wave-step comparison: kernel-launch
      proxy counts (eqns outside Pallas interiors) for the quantized
      wave step with the fused child-scan chain on vs off.
  python profiling/profile_quant.py e2e [ROWS] [ITERS]
      Steady-state iters/sec of the bench workload with
      tpu_quantized_grad off vs on — the per-leg delta.

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


def bench_hist(rows: int, reps: int):
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import quant as Q
    from lightgbm_tpu.ops.histogram import build_histogram_onehot

    f, b = 28, 256
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 255, size=(f, rows)).astype(np.int32)
    g = rng.randn(rows).astype(np.float32)
    h = (np.abs(rng.randn(rows)) + 0.01).astype(np.float32)
    bag = np.ones(rows, np.float32)

    j_bins = jnp.asarray(bins)
    gd, hd, sg, sh = Q.quantize_gradients(
        jnp.asarray(g), jnp.asarray(h), jnp.asarray(bag), jnp.int32(0),
        jnp.max(jnp.abs(jnp.asarray(g))), jnp.max(jnp.asarray(h)))
    w3 = jnp.stack([jnp.asarray(g), jnp.asarray(h), jnp.asarray(bag)])
    w2 = jnp.stack([gd, hd])
    gq = jnp.rint(gd / sg).astype(jnp.int32)
    hq = jnp.rint(hd / sh).astype(jnp.int32)

    @jax.jit
    def f32_3lane(bu, w):
        return build_histogram_onehot(bu, w, num_bins=b)

    @jax.jit
    def quant_2lane(bu, w, inv_sh):
        h2 = build_histogram_onehot(bu, w, num_bins=b)
        hh = jnp.concatenate([h2, h2[:, :, 1:2]], axis=2)
        return hh * jnp.stack([jnp.float32(1.0), jnp.float32(1.0), inv_sh])

    @jax.jit
    def packed(bu, a, c):
        return Q.hist_accumulate_packed_chunked(bu, a, c, num_bins=b)[0]

    legs = [
        ("f32 3-lane onehot", lambda: f32_3lane(j_bins, w3)),
        ("quant 2-lane onehot", lambda: quant_2lane(j_bins, w2,
                                                    1.0 / sh)),
        ("packed int32 chunked", lambda: packed(j_bins, gq, hq)),
    ]
    base = None
    for name, fn in legs:
        out = fn()
        _sync(out.astype(jnp.float32))
        t0 = time.time()
        for _ in range(reps):
            out = fn()
        _sync(out.astype(jnp.float32))
        ms = (time.time() - t0) / reps * 1e3
        if base is None:
            base = ms
        print(f"rows={rows}  {name}: {ms:.2f} ms  "
              f"vs f32 {base / ms:.2f}x")


def bench_fused(rows: int, reps: int):
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner_wave import WaveTPUTreeLearner

    def count(jaxpr, *, into_pallas):
        n = 0
        for eqn in jaxpr.eqns:
            n += 1
            if eqn.primitive.name == "pallas_call" and not into_pallas:
                continue
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else [v]
                for s in vs:
                    # control-flow params are ClosedJaxprs (.jaxpr);
                    # pallas_call carries a RAW Jaxpr (.eqns directly)
                    inner = s if hasattr(s, "eqns") \
                        else getattr(s, "jaxpr", None)
                    if inner is not None:
                        n += count(inner, into_pallas=into_pallas)
        return n

    rng = np.random.RandomState(0)
    X = rng.randn(rows, 4)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1,
              "tpu_quantized_grad": "on", "tpu_wave_pallas_scan": "on"}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    for fused in (True, False):
        ln = WaveTPUTreeLearner(Config.from_params(params), ds.constructed)
        if not fused:
            ln._fused_ok = lambda: False
        z = jnp.zeros(ds.constructed.num_data_padded, jnp.float32)
        fm = jnp.ones(ln.num_features, bool)
        jx = jax.make_jaxpr(ln._train_tree_wave)(
            ln.bins_packed(), z, z, z, fm)
        launches = count(jx.jaxpr, into_pallas=False)
        total = count(jx.jaxpr, into_pallas=True)
        print(f"fused={fused}: launch-proxy eqns={launches} "
              f"(total incl. kernel interiors={total})")


def bench_e2e(rows: int, iters: int):
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    f = 28
    X = rng.randn(rows, f).astype(np.float64)
    logit = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] * 0.5 + np.sin(X[:, 3])
             + 0.5 * rng.randn(rows))
    y = (logit > 0).astype(np.float64)
    for mode in ("off", "on"):
        params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
                  "learning_rate": 0.1, "min_data_in_leaf": 20,
                  "verbosity": -1, "metric": "none",
                  "tpu_quantized_grad": mode}
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
        print(f"quantized_grad={mode}: {iters / dt:.3f} iters/s "
              f"({dt / iters * 1e3:.1f} ms/iter)")
        del bst, ds
        gc.collect()


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "hist"
    rows = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
    reps = int(sys.argv[3]) if len(sys.argv) > 3 else 10
    if mode == "hist":
        bench_hist(rows, reps)
    elif mode == "fused":
        bench_fused(rows if len(sys.argv) > 2 else 512, reps)
    else:
        bench_e2e(rows, reps)
