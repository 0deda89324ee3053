"""Pallas TPU histogram kernel.

The TPU replacement for the reference's OpenCL histogram kernels
(`src/treelearner/ocl/histogram256.cl:343-360` and the 16/64 variants).  The
OpenCL design builds per-workgroup sub-histograms in local memory with float
atomics and then reduces; atomics do not exist on the TPU vector unit, so the
kernel instead expands each row-block's bin codes into a one-hot matrix in
VMEM and contracts it against the weight channels on the MXU:

    out[f, c, b] += w[c, r_blk] @ (bins[f, r_blk] == b)

Grid is (feature_tiles × row_blocks); the row-block axis is the sequential
reduction dimension, accumulating into the same output block (the analogue of
the OpenCL kernel's ``POWER_FEATURE_WORKGROUPS`` sub-histogram reduction).

Layout notes:
  * bins arrive (F, N) uint8 — feature-major so a block is (Ft, Rb) with rows
    contiguous in lanes.
  * weights arrive (3, N) f32: (grad·m, hess·m, m).
  * out is (F, 3, B_pad) f32, transposed to the (F, B, 3) canonical layout by
    the caller; B is padded to a lane multiple (128).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _tile_params(fw: int, n: int, word_tile: int, row_block: int,
                 num_bins: int):
    """Shared Mosaic tiling normalization for the packed-word kernels:
    word tile must divide fw and be 8-aligned (or the whole axis), the row
    block must divide n and stay >= 128 lanes, bins pad to a lane multiple.
    Returns (word_tile, rb, b_pad)."""
    if fw % word_tile or (word_tile % 8 and word_tile != fw):
        word_tile = 8 if fw % 8 == 0 else fw
    rb = min(row_block, n)
    while n % rb:
        rb //= 2
    assert rb >= 128, (n, row_block)
    return word_tile, rb, _round_up(num_bins, 128)


def _hist_kernel(bins_ref, w_ref, out_ref, *, num_bins_padded: int,
                 feature_tile: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w_blk = w_ref[...]  # (3, Rb) f32
    rb = w_blk.shape[1]

    def body(f, _):
        row = bins_ref[f, :].astype(jnp.int32)  # (Rb,)
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (num_bins_padded, rb), 0)
        onehot = (row[None, :] == iota_b).astype(jnp.float32)  # (B, Rb)
        # HIGHEST precision: default MXU passes would round the f32 grads to
        # bf16 (~1e-3 relative error per histogram sum — enough to change
        # split choices); the one-hot operand is exact either way.
        part = jax.lax.dot_general(
            w_blk, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)  # (3, B)
        out_ref[f, :, :] += part
        return 0

    jax.lax.fori_loop(0, feature_tile, body, 0, unroll=True)


@functools.partial(jax.jit, static_argnames=("num_bins", "feature_tile",
                                             "row_block"))
def build_histogram_pallas(bins: jax.Array, w: jax.Array, *, num_bins: int,
                           feature_tile: int = 8, row_block: int = 2048
                           ) -> jax.Array:
    """hist[f,b,c] = Σ_r [bins[f,r]==b] · w[r,c] via a Pallas TPU kernel.

    bins : (F, N) uint8/uint16, F a multiple of ``feature_tile`` (the dataset
           pads features), N a multiple of ``row_block``.
    w    : (N, 3) or (3, N) f32.
    Returns (F, num_bins, 3) f32.
    """
    f, n = bins.shape
    if w.ndim == 2 and w.shape[0] == n:
        w = w.T
    assert f % feature_tile == 0, (f, feature_tile)
    rb = min(row_block, n)
    while n % rb:  # rows are padded to a multiple of 1024 by the dataset
        rb //= 2
    assert rb >= 128, (n, row_block)
    b_pad = _round_up(num_bins, 128)
    grid = (f // feature_tile, n // rb)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_bins_padded=b_pad,
                          feature_tile=feature_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((feature_tile, rb), lambda i, j: (i, j)),
            pl.BlockSpec((3, rb), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((feature_tile, 3, b_pad), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, 3, b_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="build_histogram_pallas",
    )(bins, w)
    return out[:, :, :num_bins].transpose(0, 2, 1)


# ---------------------------------------------------------------------------
# Packed-word kernel for the compacted learner.
#
# Bin codes arrive packed 4-per-int32 word (feature 4k+s in byte s of word k)
# so the partition sort moves 4 features per payload operand.  The weight
# channels are split into ``nterms`` bf16 terms (w ≈ hi + lo, the one-hot
# operand is exact in bf16).  CONTRACT: weight channel 2 (the count
# channel) is a {0,1} bag mask — exactly representable in bf16 — so it
# carries ONE term while grad/hess carry ``nterms`` each
# (``_expand_terms_mixed``: 2·nterms+1 MXU rows instead of 3·nterms).
# Each grad/hess weight carries ~8·nterms mantissa bits
# (nterms=2 → ~16 bits, noticeably below f32's 24; accumulation itself is
# f32).  That is coarser than the reference GPU kernels' full-f32 regime
# (`docs/GPU-Performance.rst:137-141`) but runs at nterms MXU passes instead
# of the ~6-pass ``Precision.HIGHEST`` emulation; near-tie splits can differ
# from the f32 path.  ``nterms=3`` (~24 bits) or the config knob
# ``tpu_hist_precision=highest`` (full f32 emulation) recover f32-grade
# histograms for validation runs.
# ---------------------------------------------------------------------------


def _expand_terms(w_blk, nterms):
    """bf16 term expansion stacked along the channel axis: residual after
    t terms carries ~8(t+1) mantissa bits; (3*nterms, Rb)."""
    terms = []
    resid = w_blk
    for _ in range(nterms):
        t = resid.astype(jnp.bfloat16)
        terms.append(t)
        resid = resid - t.astype(jnp.float32)
    return jnp.concatenate(terms, axis=0)


def _expand_terms_mixed(w_blk, nterms):
    """Term expansion exploiting the count channel's exactness: w_blk rows
    are (g·bag, h·bag, bag) and bag ∈ {0,1} is exactly representable in
    bf16, so the count channel needs ONE term while g/h carry ``nterms``
    each (the dropped count residuals are exact zeros — bit-identical
    histograms, 2 fewer MXU rows at nterms=3).  Layout: g terms, then h
    terms, then the single count row — ``_reduce_mixed`` matches it."""
    gt, ht = [], []
    rg, rh = w_blk[0:1], w_blk[1:2]
    for _ in range(nterms):
        tg = rg.astype(jnp.bfloat16)
        th = rh.astype(jnp.bfloat16)
        gt.append(tg)
        ht.append(th)
        rg = rg - tg.astype(jnp.float32)
        rh = rh - th.astype(jnp.float32)
    return jnp.concatenate(gt + ht + [w_blk[2:3].astype(jnp.bfloat16)],
                           axis=0)                    # (2*nterms+1, Rb)


def _reduce_mixed(part, nterms):
    """(.., 2*nterms+1, B) term-major partials → (.., 3, B) channels."""
    t = nterms
    g = part[..., 0:t, :].sum(axis=-2)
    h = part[..., t:2 * t, :].sum(axis=-2)
    c = part[..., 2 * t, :]
    return jnp.stack([g, h, c], axis=-2)


def _expand_terms_quant(w_blk):
    """Quantized-gradient expansion (ops/quant.py): the grad/hess lanes
    are power-of-two-scaled small integers, EXACT in bf16 — one term per
    lane and NO count row (the count channel is synthesized from the
    hessian lane: Σhq hessian-mass proxy, rescaled by 1/sh outside the
    kernel).  TWO MXU rows instead of 2·nterms+1, with zero
    representation error."""
    return w_blk[0:2].astype(jnp.bfloat16)              # (2, Rb)


def _reduce_quant(part):
    """(.., 2, B) quant partials → (.., 3, B) channels; the count channel
    carries the hessian lane (Σhq·sh — the caller's 1/sh rescale recovers
    the integer hessian mass)."""
    g = part[..., 0, :]
    h = part[..., 1, :]
    return jnp.stack([g, h, h], axis=-2)


def _radix_word(wt, word, rb: int, bp: int, nterms: int,
                quant: bool = False):
    """One packed word's 4 sub-feature histogram partials via a TWO-LEVEL
    bin decomposition (the TPU analogue of the OpenCL kernels' bin-size
    specialization, `src/treelearner/ocl/histogram16.cl` vs `256.cl`):
    bin = 32·hi + lo.  The 8-wide hi one-hot FOLDS INTO THE WEIGHT OPERAND
    (A = wt ⊗ hi-onehot, cheap) and only the 32-wide lo one-hot is built
    per sub-feature — ~2.6× less VPU work than materializing the 256-wide
    one-hot, which is the packed kernels' measured floor (~6 ms per 1M-row
    pass on v5e).  The four sub-features batch into ONE
    ``(4·nt·HI, Rb) × (Rb, 128)`` MXU dot per word (cross-sub-feature
    products are discarded — the waste equals what lane padding would cost
    on per-sub-feature dots, and one dot keeps the round-4 rule that MXU
    dispatch count, not FLOPs, dominates).  Each output bucket receives
    exactly the rows of its bin, accumulated in the same row order as the
    one-hot formulation.  Returns a LIST of four (3, HI, 32) channel
    blocks — the lane dimension stays 32 end-to-end (Mosaic cannot
    shape-cast across lanes), so callers accumulate into a
    (…, 4·HI, 32) output and flatten to bins OUTSIDE the kernel."""
    nt = wt.shape[0]
    hi_n = bp // 32
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (hi_n, rb), 0)
    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (32, rb), 0)
    a_parts, lo_parts = [], []
    for s in range(4):
        code = (word >> (8 * s)) & 0xFF
        hi_oh = ((code >> 5)[None, :] == iota_hi).astype(jnp.bfloat16)
        lo_parts.append(((code & 31)[None, :] == iota_lo)
                        .astype(jnp.bfloat16))
        a_parts.append((hi_oh[None, :, :] * wt[:, None, :])
                       .reshape(nt * hi_n, rb))
    a = jnp.concatenate(a_parts, axis=0)        # (4*nt*HI, Rb)
    lo = jnp.concatenate(lo_parts, axis=0)      # (128, Rb)
    part = jax.lax.dot_general(
        a, lo, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (4*nt*HI, 128)
    outs = []
    for s in range(4):
        blk = part[s * nt * hi_n:(s + 1) * nt * hi_n,
                   s * 32:(s + 1) * 32]         # (nt*HI, 32)
        b3 = blk.reshape(nt, hi_n, 32)          # leading split only
        if quant:
            outs.append(jnp.stack([b3[0], b3[1], b3[1]]))  # (3, HI, 32)
            continue
        g = b3[0:nterms].sum(axis=0)
        h = b3[nterms:2 * nterms].sum(axis=0)
        outs.append(jnp.stack([g, h, b3[2 * nterms]]))   # (3, HI, 32)
    return outs


def _hist_kernel_packed(bins_ref, w_ref, out_ref, *, num_bins_padded: int,
                        word_tile: int, nterms: int, radix: bool = False,
                        quant: bool = False):
    # ONE dot per word: the 4 sub-features' one-hots concatenate along the
    # output axis and the bf16 terms stack along the channel axis, so each
    # word costs a single (3*nterms, Rb) x (Rb, 4*B) MXU contraction
    # instead of 4*nterms skinny ones — measured 6x on v5e
    # (round-5 chip sweep of the kernel variants)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w_blk = w_ref[...]  # (3, Rb) f32
    rb = w_blk.shape[1]
    bp = num_bins_padded
    if radix and (nterms > 0 or quant):
        wt = _expand_terms_quant(w_blk) if quant \
            else _expand_terms_mixed(w_blk, nterms)
        hi_n = bp // 32
        for wd in range(word_tile):
            accs = _radix_word(wt, bins_ref[wd, :], rb, bp, nterms,
                               quant=quant)
            for s in range(4):
                out_ref[wd, :, s * hi_n:(s + 1) * hi_n, :] += accs[s]
        return
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (bp, rb), 0)
    if nterms > 0 or quant:
        wt = _expand_terms_quant(w_blk) if quant \
            else _expand_terms_mixed(w_blk, nterms)  # (2*nterms+1, Rb)
        for wd in range(word_tile):
            word = bins_ref[wd, :]  # (Rb,) int32
            ohs = [(((word >> (8 * s)) & 0xFF)[None, :] == iota_b)
                   .astype(jnp.bfloat16) for s in range(4)]
            oh = jnp.concatenate(ohs, axis=0)    # (4B, Rb)
            part = jax.lax.dot_general(
                wt, oh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (2*nterms+1, 4B)
            out_ref[wd, :, :] += _reduce_quant(part) if quant \
                else _reduce_mixed(part, nterms)
    else:  # nterms == 0: full f32 emulation (tpu_hist_precision=highest)
        for wd in range(word_tile):
            word = bins_ref[wd, :]
            ohs = [(((word >> (8 * s)) & 0xFF)[None, :] == iota_b)
                   .astype(jnp.float32) for s in range(4)]
            oh = jnp.concatenate(ohs, axis=0)
            part = jax.lax.dot_general(
                w_blk, oh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            out_ref[wd, :, :] += part


@functools.partial(jax.jit, static_argnames=("num_bins", "word_tile",
                                             "row_block", "nterms",
                                             "radix", "quant", "interpret"))
def build_histogram_packed(bins_words: jax.Array, w: jax.Array, *,
                           num_bins: int, word_tile: int = 2,
                           row_block: int = 2048, nterms: int = 2,
                           radix: Optional[bool] = None,
                           quant: bool = False,
                           interpret: bool = False) -> jax.Array:
    """hist[f,b,c] = Σ_r [byte(bins_words[f//4,r], f%4)==b] · w[c,r].

    bins_words : (Fw, S) int32 — 4 features per word, Fw a multiple of
                 ``word_tile``; S a multiple of 1024.
    w          : (3, S) f32 — (g·m, h·m, m), already masked; channel 2
                 MUST be a {0,1} bag mask (the mixed bf16 term expansion
                 gives the count channel one exact term).
    quant      : quantized-gradient mode (ops/quant.py): w rows 0/1 are
                 pow2-scaled integers (bf16-exact, one term each), row 2
                 is ignored and the count channel returns Σ(h lane) — the
                 caller rescales it by 1/sh to the Σhq hessian-mass
                 proxy.
    Returns (Fw*4, num_bins, 3) f32.
    """
    fw, s = bins_words.shape
    word_tile, rb, b_pad = _tile_params(fw, s, word_tile, row_block,
                                        num_bins)
    if radix is None:
        radix = (nterms > 0 or quant) and b_pad % 32 == 0
    grid = (fw // word_tile, s // rb)
    in_specs = [
        pl.BlockSpec((word_tile, rb), lambda i, j: (i, j)),
        pl.BlockSpec((3, rb), lambda i, j: (0, j)),
    ]
    if radix:
        # radix output keeps the 32-lane (…, HI, 32) layout; the flatten
        # to bins is an XLA reshape outside the kernel
        hi_n = b_pad // 32
        out_specs = pl.BlockSpec((word_tile, 3, 4 * hi_n, 32),
                                 lambda i, j: (i, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((fw, 3, 4 * hi_n, 32), jnp.float32)
    else:
        out_specs = pl.BlockSpec((word_tile, 3, 4 * b_pad),
                                 lambda i, j: (i, 0, 0))
        out_shape = jax.ShapeDtypeStruct((fw, 3, 4 * b_pad), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_hist_kernel_packed, num_bins_padded=b_pad,
                          word_tile=word_tile, nterms=nterms, radix=radix,
                          quant=quant),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="build_histogram_packed",
    )(bins_words, w)
    # (fw, 3, 4, B) -> (fw*4, B, 3)
    out = out.reshape(fw, 3, 4, b_pad).transpose(0, 2, 3, 1) \
        .reshape(fw * 4, b_pad, 3)
    return out[:, :num_bins]


# ---------------------------------------------------------------------------
# Segment (multi-window) kernel for the frontier-wave learner.
#
# One wave needs the smaller-child histogram of up to W split members at
# once; windows are arbitrary disjoint ranges of the leaf-compacted row
# axis.  Instead of W sequential dynamic-slice dispatches (~0.15 ms of
# switch+launch infra each), the wave issues ONE call whose grid walks a
# scalar-prefetched chunk list: chunk t reads row-block ``block[t]`` of the
# full array, masks rows by ``lid == leaf[t]``, and accumulates into output
# slot ``slot[t]``.  Chunks are member-major so slot revisits are
# consecutive (the standard Pallas reduction pattern); tail padding uses
# slot == n_slots and is skipped entirely (its block-0 DMA is the only
# cost).  Boundary blocks shared by two members appear once per member —
# the lid mask makes the split exact regardless of alignment.
# ---------------------------------------------------------------------------


def _hist_kernel_segment(slot_ref, block_ref, leaf_ref, bins_ref, w_ref,
                         lid_ref, out_ref, *, num_bins_padded: int,
                         word_tile: int, nterms: int, n_slots: int,
                         radix: bool = False, quant: bool = False):
    t = pl.program_id(1)
    slot = slot_ref[t]
    prev = slot_ref[jnp.maximum(t - 1, 0)]
    first = (t == 0) | (slot != prev)

    @pl.when(slot < n_slots)
    def _compute():
        @pl.when(first)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        leaf = leaf_ref[t]
        lid_blk = lid_ref[...]
        m = (lid_blk == leaf).astype(jnp.float32)[None, :]
        w_blk = w_ref[...] * m                      # (3, Rb) masked
        rb = w_blk.shape[1]
        bp = num_bins_padded
        if radix and (nterms > 0 or quant):
            wt = _expand_terms_quant(w_blk) if quant \
                else _expand_terms_mixed(w_blk, nterms)
            hi_n = bp // 32
            for wd in range(word_tile):
                accs = _radix_word(wt, bins_ref[wd, :], rb, bp, nterms,
                                   quant=quant)
                for sf in range(4):
                    out_ref[0, wd, :, sf * hi_n:(sf + 1) * hi_n, :] += \
                        accs[sf]
            return
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (bp, rb), 0)
        if quant:
            wt = _expand_terms_quant(w_blk)          # (2, Rb)
        elif nterms > 0:
            wt = _expand_terms_mixed(w_blk, nterms)  # (2*nterms+1, Rb)
        for wd in range(word_tile):
            word = bins_ref[wd, :]
            ohdt = jnp.bfloat16 if (nterms > 0 or quant) else jnp.float32
            ohs = [(((word >> (8 * s)) & 0xFF)[None, :] == iota_b)
                   .astype(ohdt) for s in range(4)]
            oh = jnp.concatenate(ohs, axis=0)       # (4B, Rb)
            if nterms > 0 or quant:
                part = jax.lax.dot_general(
                    wt, oh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # (2*nterms+1, 4B)
                acc = _reduce_quant(part) if quant \
                    else _reduce_mixed(part, nterms)
            else:
                acc = jax.lax.dot_general(
                    w_blk, oh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
            out_ref[0, wd, :, :] += acc             # (3, 4B)


@functools.partial(jax.jit, static_argnames=("num_bins", "n_slots",
                                             "word_tile", "row_block",
                                             "nterms", "quant",
                                             "interpret"))
def build_histogram_segments(bins_words: jax.Array, w: jax.Array,
                             lid: jax.Array, chunk_slot: jax.Array,
                             chunk_block: jax.Array, chunk_leaf: jax.Array,
                             *, num_bins: int, n_slots: int,
                             word_tile: int = 2, row_block: int = 2048,
                             nterms: int = 2, radix: Optional[bool] = None,
                             quant: bool = False,
                             interpret: bool = False
                             ) -> jax.Array:
    """Per-slot histograms over lid-masked row chunks (see block comment).

    bins_words : (Fw, N) int32 packed codes; w (3, N) f32 with channel 2 a
                 {0,1} bag mask (see ``build_histogram_packed``); lid (N,)
                 int32.  ``quant`` as in ``build_histogram_packed``.
    chunk_*    : (T,) int32 — output slot (== n_slots ⇒ no-op), row-block
                 index, and lid value per chunk; slots non-decreasing.
    Returns (n_slots, Fw*4, num_bins, 3) f32.
    """
    fw, n = bins_words.shape
    word_tile, rb, b_pad = _tile_params(fw, n, word_tile, row_block,
                                        num_bins)
    if radix is None:
        radix = (nterms > 0 or quant) and b_pad % 32 == 0
    grid = (fw // word_tile, chunk_slot.shape[0])
    if radix:
        hi_n = b_pad // 32
        out_specs = pl.BlockSpec((1, word_tile, 3, 4 * hi_n, 32),
                                 lambda i, t, s, b, l: (s[t], i, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct(
            (n_slots + 1, fw, 3, 4 * hi_n, 32), jnp.float32)
    else:
        out_specs = pl.BlockSpec((1, word_tile, 3, 4 * b_pad),
                                 lambda i, t, s, b, l: (s[t], i, 0, 0))
        out_shape = jax.ShapeDtypeStruct((n_slots + 1, fw, 3, 4 * b_pad),
                                         jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((word_tile, rb),
                         lambda i, t, s, b, l: (i, b[t])),
            pl.BlockSpec((3, rb), lambda i, t, s, b, l: (0, b[t])),
            pl.BlockSpec((rb,), lambda i, t, s, b, l: (b[t],)),
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        functools.partial(_hist_kernel_segment, num_bins_padded=b_pad,
                          word_tile=word_tile, nterms=nterms,
                          n_slots=n_slots, radix=radix, quant=quant),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="build_histogram_segments",
    )(chunk_slot, chunk_block, chunk_leaf, bins_words, w, lid)
    # (S, fw, 3, 4, B) -> (S, fw*4, B, 3)
    out = out[:n_slots].reshape(n_slots, fw, 3, 4, b_pad) \
        .transpose(0, 1, 3, 4, 2).reshape(n_slots, fw * 4, b_pad, 3)
    return out[:, :, :num_bins]


# ---------------------------------------------------------------------------
# Multi-slot full-pass kernel for the wave learner's LEVEL OPENING.
#
# The first tree levels run UNSORTED (rows stay in root order, only the
# per-row leaf-id lane advances), so the segment kernel's chunk walk — which
# needs each member's rows physically contiguous — cannot serve them.  This
# kernel histograms K leaves in ONE pass over the full row axis.  Two
# formulations.  PLAIN (``tpu_hist_precision=highest``, or a K with no
# split): the 256-wide bin one-hot of ``build_histogram_packed``'s plain
# branch is built once and shared across slots, and slot routing rides the
# weight operand, ``(K·3·nterms, Rb) × (Rb, 4·B)`` a word; its one-hot alone
# costs 60 ms a pass at 10.5M rows x 8 words whatever K is, against the
# 21.6 ms of the radix root pass (my chip runs, PR 31: 60.0 / 64.7 / 71.5 /
# 87.5 / 143.7 / 334.6 ms at K = 1 / 2 / 4 / 8 / 16 / 32).  RADIX (the
# default, as in the other two kernels): ``_radix_word_slots`` splits the
# slot index as ``_radix_word`` splits the bin, low part on the column side
# with the 32-wide lo one-hot, high part on the weight side with the hi
# one-hot: 27.6 / 32.7 / 31.1 / 52.6 / 102.5 / 202.1 ms at the same K with
# the words unrolled, 36 / 42 / 40 / 63 / 116 to K = 16 looped a word at a
# time, 32 / 37 / 36 / 58 / 110 looped two words a body (this kernel).
# ---------------------------------------------------------------------------


def _radix_word_slots(wt_g, word, cs, rb: int, bp: int, nterms: int,
                      n_col: int, quant: bool = False):
    """``_radix_word`` for K = G x C slots: one packed word's partials of
    every slot.  The slot index splits as the bin does: its low part ``cs``
    (C = ``n_col`` values, at most 4) rides the COLUMN side with the 32-wide
    lo one-hot (column 32 x cs + lo), its high part the weight side
    (``wt_g[g]``: the bf16 terms of the rows of row group g, zero
    elsewhere), stacked with the hi one-hot.  A dot takes as many
    sub-features as fill its 128 columns (4 // C), so no product is wasted
    once C = 4 and K = 1 is ``_radix_word`` itself: the MXU streams
    ``G x 224`` rows a word and the VPU builds ``128 x C`` one-hot rows,
    where the 256-wide one-hot of the plain formulation costs 1024 whatever
    K is.  Returns ``[(c, sub-feature, (G, 3, HI, 32))]``: the block of the
    G slots ``c x G .. c x G + G`` in the kernel's COLUMN-MAJOR slot order
    (the wrapper puts the slots back in order)."""
    nt = wt_g[0].shape[0]
    n_grp = len(wt_g)
    hi_n = bp // 32
    per_dot = 4 // n_col                     # sub-features a dot
    width = 32 * n_col                       # columns a sub-feature
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (hi_n, rb), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (width, rb), 0)
    outs = []
    for d in range(n_col):
        a_parts, lo_parts = [], []
        for s in range(d * per_dot, (d + 1) * per_dot):
            code = (word >> (8 * s)) & 0xFF
            hi_oh = ((code >> 5)[None, :] == iota_hi).astype(jnp.bfloat16)
            col = (code & 31) + (cs << 5)
            lo_parts.append((col[None, :] == iota_c).astype(jnp.bfloat16))
            for wt in wt_g:
                a_parts.append((hi_oh[None, :, :] * wt[:, None, :])
                               .reshape(nt * hi_n, rb))
        a = jnp.concatenate(a_parts, axis=0)    # (per_dot*G*nt*HI, Rb)
        lo = jnp.concatenate(lo_parts, axis=0)  # (128, Rb)
        part = jax.lax.dot_general(
            a, lo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # leading split only: the 128 lanes stay whole until sliced
        p5 = part.reshape(per_dot, n_grp, nt, hi_n, 128)
        if quant:
            g, h = p5[:, :, 0], p5[:, :, 1]
            acc = jnp.stack([g, h, h], axis=2)
        else:
            g, h = p5[:, :, 0], p5[:, :, nterms]
            for t in range(1, nterms):
                g = g + p5[:, :, t]
                h = h + p5[:, :, nterms + t]
            acc = jnp.stack([g, h, p5[:, :, 2 * nterms]], axis=2)
        for i in range(per_dot):                # (G, 3, HI, 128) each
            for c in range(n_col):
                c0 = i * width + c * 32
                outs.append((c, d * per_dot + i, acc[i, :, :, :, c0:c0 + 32]))
    return outs


def _multislot_split(n_slots: int):
    """(column slots C, row groups G) of ``_radix_word_slots``: C x G =
    ``n_slots`` with C a power of two up to 4; None where there is no such
    split (the plain formulation serves)."""
    if n_slots in (1, 2):
        return n_slots, 1
    return (4, n_slots // 4) if n_slots % 4 == 0 else None


# the largest output block (words x slots) that the compiler's default scoped
# VMEM (16 MiB) has held on the chip: the Higgs cells' 8 words at 16 slots.
# With its output in HBM, as inside a tree step, a pass asks 8.75 MiB at
# 8 x 16, 8.75 at 18 x 8, 16.5 at 18 x 16, 14.75 at 56 and 64 words x 16 in
# tiles of 8 (13.5 at the ranking cell's row block of 1024), two words a loop
# body or one (compiled ahead of time for a v5e); a shard of the four-chip
# cell overran the default on the chip at 18 x 16
_MULTISLOT_DEFAULT_VMEM_BLOCK = 8 * 16


def _multislot_vmem_limit(word_tile: int, n_slots: int,
                          b_pad: int) -> Optional[int]:
    """Scoped VMEM of a multi-slot pass: the compiler's default (None) up
    to the output block the chip has compiled within it, above it six
    output blocks and at least 32 MiB."""
    if word_tile * n_slots <= _MULTISLOT_DEFAULT_VMEM_BLOCK:
        return None
    block = word_tile * n_slots * 3 * 4 * b_pad * 4
    return min(max(32 << 20, 6 * block), 100 << 20)


def _hist_kernel_multislot(bins_ref, w_ref, slot_ref, out_ref, *,
                           num_bins_padded: int, word_tile: int, nterms: int,
                           n_slots: int, radix: bool = False,
                           quant: bool = False):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w_blk = w_ref[...]          # (3, Rb) f32
    slot_blk = slot_ref[...]    # (Rb,) int32; >= n_slots means masked
    rb = w_blk.shape[1]
    bp = num_bins_padded
    if radix:
        n_col, n_grp = _multislot_split(n_slots)
        wt = _expand_terms_quant(w_blk) if quant \
            else _expand_terms_mixed(w_blk, nterms)
        grp = slot_blk >> (n_col.bit_length() - 1)
        # a row outside [0, n_slots) falls in no row group
        wt_g = [jnp.where((grp == g)[None, :], wt, jnp.zeros_like(wt))
                for g in range(n_grp)]
        cs = slot_blk & (n_col - 1)
        hi_n = bp // 32

        def one_word(wd):
            for c, s, acc in _radix_word_slots(
                    wt_g, bins_ref[wd, :], cs, rb, bp, nterms, n_col,
                    quant=quant):
                out_ref[wd, c * n_grp:(c + 1) * n_grp, :,
                        s * hi_n:(s + 1) * hi_n, :] += acc

        # a loop, not an unrolled body: a kernel of 8 unrolled words costs
        # the host 8 times the lowering in every job's first iteration,
        # compile cache or not.  Two words a body: the scheduler does not
        # reach across the loop's boundary, so a body's first dot waits
        # with the MXU idle while its operands are built, and the second
        # word's first operands are built under the first word's dots
        def two_words(i, carry):
            one_word(2 * i)
            one_word(2 * i + 1)
            return carry

        jax.lax.fori_loop(0, word_tile // 2, two_words, 0)
        if word_tile % 2:
            one_word(word_tile - 1)
        return
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (n_slots, rb), 0)
    soh = slot_blk[None, :] == iota_s                      # (K, Rb) bool
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (bp, rb), 0)
    if nterms > 0 or quant:
        wt = _expand_terms_quant(w_blk) if quant \
            else _expand_terms_mixed(w_blk, nterms)    # (2T+1, Rb) bf16
        nt = wt.shape[0]
        a = (soh.astype(jnp.bfloat16)[:, None, :] * wt[None, :, :]) \
            .reshape(n_slots * nt, rb)
        for wd in range(word_tile):
            word = bins_ref[wd, :]
            ohs = [(((word >> (8 * s)) & 0xFF)[None, :] == iota_b)
                   .astype(jnp.bfloat16) for s in range(4)]
            oh = jnp.concatenate(ohs, axis=0)              # (4B, Rb)
            part = jax.lax.dot_general(
                a, oh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # (K*nt, 4B)
            p3 = part.reshape(n_slots, nt, 4 * bp)
            acc = _reduce_quant(p3) if quant \
                else _reduce_mixed(p3, nterms)
            out_ref[wd, :, :, :] += acc
    else:  # full f32 emulation (tpu_hist_precision=highest)
        a = (soh.astype(jnp.float32)[:, None, :] * w_blk[None, :, :]) \
            .reshape(n_slots * 3, rb)
        for wd in range(word_tile):
            word = bins_ref[wd, :]
            ohs = [(((word >> (8 * s)) & 0xFF)[None, :] == iota_b)
                   .astype(jnp.float32) for s in range(4)]
            oh = jnp.concatenate(ohs, axis=0)
            part = jax.lax.dot_general(
                a, oh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            out_ref[wd, :, :, :] += part.reshape(n_slots, 3, 4 * bp)


@functools.partial(jax.jit, static_argnames=("num_bins", "n_slots",
                                             "word_tile", "row_block",
                                             "nterms", "quant",
                                             "interpret"))
def build_histogram_multislot(bins_words: jax.Array, w: jax.Array,
                              slot: jax.Array, *, num_bins: int,
                              n_slots: int, word_tile: int = 2,
                              row_block: int = 2048, nterms: int = 2,
                              quant: bool = False,
                              interpret: bool = False) -> jax.Array:
    """Per-slot histograms over the FULL row axis in one pass.

    bins_words : (Fw, N) int32 packed codes; w (3, N) f32 (already masked
                 by bag); slot (N,) int32 — output slot per row, any value
                 outside [0, n_slots) contributes nowhere.  ``quant`` as
                 in ``build_histogram_packed``.
    Returns (n_slots, Fw*4, num_bins, 3) f32.
    """
    fw, n = bins_words.shape
    word_tile, rb, b_pad = _tile_params(fw, n, word_tile, row_block,
                                        num_bins)
    split = _multislot_split(n_slots)
    radix = (nterms > 0 or quant) and split is not None
    grid = (fw // word_tile, n // rb)
    if radix:
        # the 32-lane (..., HI, 32) layout of the other radix kernels
        hi_n = b_pad // 32
        out_specs = pl.BlockSpec((word_tile, n_slots, 3, 4 * hi_n, 32),
                                 lambda i, j: (i, 0, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((fw, n_slots, 3, 4 * hi_n, 32),
                                         jnp.float32)
    else:
        out_specs = pl.BlockSpec((word_tile, n_slots, 3, 4 * b_pad),
                                 lambda i, j: (i, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((fw, n_slots, 3, 4 * b_pad),
                                         jnp.float32)
    out = pl.pallas_call(
        functools.partial(_hist_kernel_multislot, num_bins_padded=b_pad,
                          word_tile=word_tile, nterms=nterms,
                          n_slots=n_slots, radix=radix, quant=quant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((word_tile, rb), lambda i, j: (i, j)),
            pl.BlockSpec((3, rb), lambda i, j: (0, j)),
            pl.BlockSpec((rb,), lambda i, j: (j,)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_multislot_vmem_limit(word_tile, n_slots,
                                                   b_pad)),
        interpret=interpret,
        name="build_histogram_multislot",
    )(bins_words, w, slot)
    if radix:
        # the kernel's slot c x G + g is slot g x C + c
        n_col, n_grp = split
        out = out.reshape(fw, n_col, n_grp, 3, 4 * hi_n, 32) \
            .transpose(0, 2, 1, 3, 4, 5)
    # (fw, K, 3, 4, B) -> (K, fw*4, B, 3)
    out = out.reshape(fw, n_slots, 3, 4, b_pad) \
        .transpose(1, 0, 3, 4, 2).reshape(n_slots, fw * 4, b_pad, 3)
    return out[:, :, :num_bins]


def pack_bin_words(bins: jax.Array) -> jax.Array:
    """(F, N) uint8 bin codes → (F/4, N) int32, feature 4k+s in byte s of
    word k.  F must already be padded to a multiple of 4; codes above 255
    do not fit a byte (the compact-learner factory routes >256-bin datasets
    to the masked learner)."""
    import jax.numpy as jnp

    f, n = bins.shape
    assert f % 4 == 0, f
    assert bins.dtype == jnp.uint8, f"packable bins must be uint8, got {bins.dtype}"
    b = bins.astype(jnp.int32).reshape(f // 4, 4, n)
    return (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24))


def pack_bin_words_host(bins):
    """:func:`pack_bin_words` on the host (numpy in, numpy out): what a
    sharded learner packs one device's rows with before it places them, so
    that no chip ever holds the unpacked table, nor another chip's rows."""
    import os
    import sys
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    f, n = bins.shape
    assert f % 4 == 0, f
    assert bins.dtype == np.uint8, \
        f"packable bins must be uint8, got {bins.dtype}"
    assert sys.byteorder == "little"
    words = np.empty((f // 4, n), np.int32)
    # byte s of word k, little-endian; written in blocks of rows that stay in
    # the cache (whole rows at a time: 44 s for 72 x 53M codes, this 0.5 s)
    lanes = words.view(np.uint8).reshape(f // 4, n, 4)
    step = 1 << 18

    def pack(a):
        for s in range(4):
            lanes[:, a:a + step, s] = bins[s::4, a:a + step]

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        list(pool.map(pack, range(0, n, step)))
    return words


def unpack_bin_words(words: jax.Array, num_features: int) -> jax.Array:
    """(Fw, S) int32 → (num_features, S) int32 bin codes."""
    import jax.numpy as jnp

    fw, s = words.shape
    parts = [(words >> (8 * i)) & 0xFF for i in range(4)]
    out = jnp.stack(parts, axis=1).reshape(fw * 4, s)
    return out[:num_features]
