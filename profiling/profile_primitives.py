"""Honest microbench of partition-primitive candidates on the real TPU.

Decides the compact learner's data-movement strategy: multi-operand
lax.sort (current) vs argsort+gather vs cumsum+scatter, plus XLA gather /
scatter raw throughput.  All timings end with a device->host fetch of the
result, which waits for it.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, iters=50):
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
    rng = np.random.RandomState(0)
    key = jnp.asarray(rng.randint(0, 2, S).astype(np.int32))
    bins = jnp.asarray(rng.randint(0, 2**31, (fw, S)).astype(np.int32))
    w3 = jnp.asarray(rng.randn(3, S).astype(np.float32))
    rid = jnp.arange(S, dtype=jnp.int32)
    perm = jnp.asarray(rng.permutation(S).astype(np.int32))

    @jax.jit
    def sort13(key, bins, w3, rid):
        ops = [key] + [bins[i] for i in range(fw)] + [w3[i] for i in range(3)] \
            + [rid, rid]
        out = lax.sort(ops, num_keys=1, is_stable=True)
        return out[1]

    @jax.jit
    def sort10(key, bins, w3, rid):
        ops = [key] + [bins[i] for i in range(fw)] + [rid]
        out = lax.sort(ops, num_keys=1, is_stable=True)
        return out[1]

    @jax.jit
    def sort2(key, rid):
        out = lax.sort([key, rid], num_keys=1, is_stable=True)
        return out[1]

    @jax.jit
    def gather_rows(bins, perm):
        return jnp.take(bins, perm, axis=1)

    @jax.jit
    def gather_1d(w, perm):
        return jnp.take(w, perm)

    @jax.jit
    def scatter_rows(bins, perm):
        return jnp.zeros_like(bins).at[:, perm].set(bins, unique_indices=True)

    @jax.jit
    def cumsum_dest(key):
        left = (key == 1)
        nl = jnp.cumsum(left.astype(jnp.int32))
        total_l = nl[-1]
        dest = jnp.where(left, nl - 1,
                         total_l + jnp.cumsum((~left).astype(jnp.int32)) - 1)
        return dest

    results = {}
    for name, fn, args in [
        ("sort 13 ops", sort13, (key, bins, w3, rid)),
        ("sort 10 ops", sort10, (key, bins, w3, rid)),
        ("sort 2 ops (key+idx)", sort2, (key, rid)),
        ("gather (8,S) rows", gather_rows, (bins, perm)),
        ("gather (S,) 1d", gather_1d, (w3[0], perm)),
        ("scatter (8,S) rows", scatter_rows, (bins, perm)),
        ("cumsum dest", cumsum_dest, (key,)),
    ]:
        t = timed(fn, *args)
        results[name] = t
        print(f"{name:24s} {t*1e3:9.2f} ms")


if __name__ == "__main__":
    main()
