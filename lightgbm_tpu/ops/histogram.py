"""Histogram construction — the hottest op.

TPU-native replacement for the reference histogram paths:
  * CPU: ``DenseBin::ConstructHistogram`` (`src/io/dense_bin.hpp:74-141`) —
    per-row scalar accumulation under OpenMP.
  * GPU: OpenCL kernels with local-memory float atomics
    (`src/treelearner/ocl/histogram256.cl:343-360`).

On TPU, scalar scatter is poison; instead the bin codes are expanded to a
one-hot matrix and contracted against the per-row weight channels on the MXU:

    hist[f, b, c] = sum_r [bins[f, r] == b] * w[r, c]

with ``w = (grad * m, hess * m, m)`` and ``m`` the leaf/bagging mask.  This is
the "sub-histogram then reduce" structure of the OpenCL kernel, re-expressed as
a matmul so XLA tiles it onto the systolic array.  Layout is
``(features, bins, 3)`` so sibling subtraction (`feature_histogram.hpp:67`) and
``FixHistogram`` (`src/io/dataset.cpp:923-942`) are trivial vector ops.

Backends:
  * ``onehot`` — pure jnp, row-block ``lax.scan`` (works everywhere; XLA fuses
    the one-hot into the dot on TPU).
  * ``pallas`` — hand-tiled TPU kernel (see ``hist_pallas.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("num_bins", "row_block", "dp"))
def build_histogram_onehot(bins: jax.Array, w: jax.Array, *, num_bins: int,
                           row_block: int = 4096, dp: bool = False
                           ) -> jax.Array:
    """hist[f,b,c] = Σ_r [bins[f,r]==b] · w[c,r].

    Parameters
    ----------
    bins : (F, N) uint8/uint16 — bin codes (padded rows must carry w=0)
    w : (C, N) f32 — weight channels, typically (g·m, h·m, m)
    dp : accumulate in f64 and RETURN f64 — the analogue of the reference's
         ``gpu_use_dp`` (`config.h:872-876`); the histogram pool and split
         scans then run in f64 end-to-end so training decisions track the
         f64 CPU reference (requires ``jax_enable_x64``).
    Returns (F, num_bins, C) f32 (f64 when dp).
    """
    f, n = bins.shape
    if w.ndim == 2 and w.shape[1] != n:
        w = w.T
    c = w.shape[0]
    rb = min(row_block, n)
    while n % rb:  # rows are padded to a multiple of 1024 by the dataset
        rb //= 2
    assert rb >= 1, (n, row_block)
    nblk = n // rb
    acc_dtype = jnp.float64 if dp else jnp.float32
    w = w.astype(acc_dtype)
    bins_r = bins.reshape(f, nblk, rb).transpose(1, 0, 2)  # (nblk, F, rb)
    w_r = w.reshape(c, nblk, rb).transpose(1, 2, 0)        # (nblk, rb, C)

    def body(acc, blk):
        b_blk, w_blk = blk                      # (F, rb) , (rb, C)
        oh = (b_blk[:, :, None] == jnp.arange(num_bins, dtype=jnp.int32)
              [None, None, :].astype(bins.dtype)).astype(acc_dtype)
        # contract rows on the MXU: (F, rb, B) × (rb, C) → (F, B, C).
        # HIGHEST precision is required: the default lets the MXU round the
        # f32 gradients to bf16, which costs ~1e-3 relative error in every
        # histogram sum and visibly degrades split gains.
        part = jax.lax.dot_general(
            oh, w_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
            precision=jax.lax.Precision.HIGHEST)
        return acc + part, None

    init = jnp.zeros((f, num_bins, c), dtype=acc_dtype)
    hist, _ = jax.lax.scan(body, init, (bins_r, w_r))
    return hist


def build_histogram(bins: jax.Array, w: jax.Array, *, num_bins: int,
                    backend: str = "auto", row_block: int = 4096,
                    dp: bool = False) -> jax.Array:
    """Dispatch histogram construction to the best backend for this platform."""
    if backend == "auto":
        backend = "pallas" if bins.ndim == 2 and _on_tpu() else "onehot"
    if backend == "pallas" and not dp:
        from .hist_pallas import build_histogram_pallas
        return build_histogram_pallas(bins, w, num_bins=num_bins)
    # dp falls back to the XLA path — f64 dots don't map onto the MXU
    return build_histogram_onehot(bins, w, num_bins=num_bins,
                                  row_block=row_block, dp=dp)


def _on_tpu() -> bool:
    """Whether the default JAX device is a TPU.  No fallback: a process
    that cannot reach its device raises here instead of silently taking
    every CPU branch."""
    return jax.devices()[0].platform == "tpu"


def fix_histogram(hist: jax.Array, default_bin: jax.Array, sum_g: jax.Array,
                  sum_h: jax.Array, cnt: jax.Array) -> jax.Array:
    """Recompute the default bin's entry from leaf totals
    (``Dataset::FixHistogram``, `src/io/dataset.cpp:923-942`).

    Not needed when histograms are built over all bins (our default), but used
    by the distributed learners after reduce-scatter of partial histograms
    where the default bin is elided from the wire format.
    """
    f, b, c = hist.shape
    totals = jnp.stack([sum_g, sum_h, cnt], axis=-1)  # (F, 3)
    others = totals[:, None, :] - hist.sum(axis=1, keepdims=True) + \
        jnp.take_along_axis(hist, default_bin[:, None, None].repeat(c, -1), axis=1)
    sel = jnp.arange(b)[None, :, None] == default_bin[:, None, None]
    return jnp.where(sel, others, hist)


def subtract_sibling(parent: jax.Array, child: jax.Array) -> jax.Array:
    """The histogram subtraction trick (`feature_histogram.hpp:67` Subtract)."""
    return parent - child
