"""In-program costs of wave-learner building blocks (one jit, chained ops).

Per-dispatch overhead masks small-op costs when each primitive is its own
jit call; the wave learner runs everything inside ONE
XLA program, so chain K repetitions with data dependencies inside a single
jit and report (t_K - t_0) / K.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, iters=20):
    import jax
    r = fn(*args)
    np.asarray(jax.tree_util.tree_leaves(r)[0].ravel()[0])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args)
        np.asarray(jax.tree_util.tree_leaves(r)[0].ravel()[0])
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = int(sys.argv[1]) if len(sys.argv) > 1 else 1_048_576
    fw = 8
    M = 768
    rng = np.random.RandomState(0)
    lid = jnp.asarray(rng.randint(0, M, S).astype(np.int32))
    table = jnp.asarray(rng.randint(0, 255, M).astype(np.int32))
    bins = jnp.asarray(rng.randint(0, 2**31, (fw, S)).astype(np.int32))
    w3 = jnp.asarray(rng.randn(3, S).astype(np.float32))
    rid = jnp.arange(S, dtype=jnp.int32)

    def chain_sorts(sizes):
        def f(key, bins, w3, rid, lid):
            out = key
            for Sp in sizes:
                kw = lax.dynamic_slice(out, (0,), (Sp,))
                bw = lax.dynamic_slice(bins, (0, 0), (fw, Sp))
                ww = lax.dynamic_slice(w3, (0, 0), (3, Sp))
                rw = lax.dynamic_slice(rid, (0,), (Sp,))
                lw = lax.dynamic_slice(lid, (0,), (Sp,))
                ops = [kw] + [bw[i] for i in range(fw)] \
                    + [ww[i] for i in range(3)] + [rw, lw]
                sd = lax.sort(ops, num_keys=1, is_stable=True)
                # depend on results so nothing is elided
                out = key + jnp.pad(sd[1], (0, S - Sp))
            return out
        return jax.jit(f)

    def chain_gathers(k):
        def f(lid, table):
            acc = jnp.zeros_like(lid)
            t = table
            for i in range(k):
                acc = acc + t[jnp.minimum(lid + acc % 3, M - 1)]
            return acc
        return jax.jit(f)

    def chain_msum(k):
        def f(widx, bins):
            acc = jnp.zeros_like(bins[0])
            for i in range(k):
                cur = jnp.zeros_like(bins[0])
                for w in range(fw):
                    cur = cur + jnp.where((widx + acc % 2) % fw == w,
                                          bins[w], 0)
                acc = acc + cur
            return acc
        return jax.jit(f)

    def chain_matmul(k):
        wave = jnp.asarray(rng.choice(M, 64, replace=False).astype(np.int32))
        bag = jnp.asarray((rng.rand(S) > 0.2).astype(np.int8))

        def f(lid, wave, bag):
            acc = jnp.zeros(64, jnp.int32)
            for i in range(k):
                m = (lid[None, :] == (wave + acc[0] % 2)[:, None]) \
                    .astype(jnp.int8)
                acc = acc + lax.dot_general(
                    m, bag[:, None], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)[:, 0]
            return acc
        return jax.jit(f), (lid, wave, bag)

    key = table[lid]
    base = timed(chain_sorts([]), key, bins, w3, rid, lid)
    print(f"S={S}  empty-chain baseline {base*1e3:8.2f} ms")

    full = [S] * 13
    shrink = []
    cur = S
    for i in range(13):
        shrink.append(max(65536, cur))
        if i % 2 == 1:
            cur //= 2
    for name, sizes in [("13x full-S sorts", full),
                        ("13x shrinking sorts", shrink),
                        ("1x full-S sort", [S])]:
        t = timed(chain_sorts(sizes), key, bins, w3, rid, lid)
        print(f"{name:26s} {(t-base)*1e3:8.2f} ms  "
              f"({(t-base)/len(sizes)*1e3:6.2f} ms/sort)")

    for k in (8,):
        t = timed(chain_gathers(k), lid, table)
        print(f"{k}x table gather (chained)  {(t-base)*1e3:8.2f} ms  "
              f"({(t-base)/k*1e3:6.2f} ms/gather)")
        widx = jnp.asarray(rng.randint(0, fw, S).astype(np.int32))
        t = timed(chain_msum(k), widx, bins)
        print(f"{k}x word masked-sum fw8    {(t-base)*1e3:8.2f} ms  "
              f"({(t-base)/k*1e3:6.2f} ms/extract)")
        fn, args = chain_matmul(k)
        t = timed(fn, *args)
        print(f"{k}x mask matmul W=64      {(t-base)*1e3:8.2f} ms  "
              f"({(t-base)/k*1e3:6.2f} ms/matmul)")


if __name__ == "__main__":
    main()
