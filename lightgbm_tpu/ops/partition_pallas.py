"""Pallas TPU stable row-partition kernel (the reference's third kernel).

The OpenCL reference ships histogram, split-scan AND a data-partition
kernel; only the histogram family had been ported.  The wave learner
re-compacts every split window with a full-array ``lax.sort``
(`learner_wave.growth_sort`: 144 ms at 10.5M rows x 7 words, 10 ms an
operand, and 62 % of an iteration in the ledger of PR 28).  Its cost does
not depend on the keys (bitonic stages), but the wave's
permutation is *not* a general sort: every row's destination is known in
closed form before any row moves —

    dest(r) = child_window_start + (stable rank of r among its
              sibling-side rows)

so the sort can be replaced by a **two-pass stable partition**:

  1. *(XLA, cheap)* per-row destinations from two exclusive prefix-sums
     over the left/right split flags (``exclusive_cumsum_i32`` — chunked
     triangular-matmul cumsums, integer-exact at any row count) plus
     per-member base constants routed through the wave's existing
     mask-matmul (no gathers over the row axis);
  2. *(Pallas)* ``apply_partition``: a scalar-prefetched chunk walk — the
     same grid structure as ``hist_pallas.build_histogram_segments`` —
     where chunk t reads source row-block ``it[t]``, selects the rows
     whose destination lands in output row-block ``ot[t]``, and
     accumulates them into that block through a one-hot MXU contraction.

Exactness: every payload lane is decomposed into **byte planes** (values
0..255, exactly representable in bf16); the one-hot matrix is 0/1 (exact
in bf16); each output element receives exactly one nonzero product, so
the bf16 contraction transports every byte bit-exactly and the int32
words / f32 weights are reassembled bitwise outside the kernel.  The
result is the *identical permutation* the stable sort produces — trees
are record-exact (tests/test_partition.py).

Chunk-list size: each split window of width ``c`` contributes
``O(c / row_block)`` chunks (each source block's left rows occupy
consecutive destinations, so they span at most two output blocks; same
for right rows; plus one identity chunk per covered block for the
unmoved rows), so kernel work scales with the *moving* rows — bottom
waves whose windows froze pay nothing, exactly like the sort skip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# Row-count ceiling: destinations/ranks ride f32-exact integer planes and
# row ids travel as 3 byte planes, both of which cap at 2^24 rows.
MAX_PARTITION_ROWS = 1 << 24
# lid travels as 2 byte planes.
MAX_PARTITION_SLOTS = 1 << 16


def partition_row_block(n: int, row_block: int = 512) -> int:
    """Largest power-of-two block <= row_block dividing n (>= 128 lanes,
    mirroring the histogram kernels' tiling rule)."""
    rb = min(row_block, n)
    while n % rb:
        rb //= 2
    assert rb >= 128, (n, row_block)
    return rb


# ---------------------------------------------------------------------------
# Pass 1 helper: integer-exact exclusive prefix sums over the row axis.
# ---------------------------------------------------------------------------


def exclusive_cumsum_i32(flags: jax.Array, chunk: int = 512) -> jax.Array:
    """(L, N) {0,1} int flags -> (L, N) int32 exclusive prefix sums.

    XLA lowers ``jnp.cumsum`` over a 1M-row axis to an O(N)-depth scan
    (~1.8 ms/M elements on v5e, round 5); the
    bin-scan trick from ``ops/split.py`` applies here too: cumsum within
    ``chunk``-sized pieces via one triangular-matrix MXU contraction plus
    a short carry cumsum over the per-chunk totals.  Exact at any N: the
    in-chunk dot sums at most ``chunk`` ones (f32-exact), carries
    accumulate in int32.
    """
    l, n = flags.shape
    c = chunk
    while n % c:
        c //= 2
    nchunk = n // c
    f = flags.reshape(l, nchunk, c).astype(jnp.float32)
    # out[..., t] = sum_{b < t} f[..., b] (exclusive): contracting over
    # the leading axis of tri, the nonzeros must sit at b < t.  Built
    # from iotas, not a numpy constant — a (c, c) f32 constant would
    # trip the analysis gate's baked-constant ceiling
    io = jnp.arange(c, dtype=jnp.int32)
    tri = (io[:, None] < io[None, :]).astype(jnp.float32)
    within = lax.dot_general(f, tri, (((2,), (0,)), ((), ())),
                             precision=lax.Precision.HIGHEST)
    within = jnp.rint(within).astype(jnp.int32)          # (L, nchunk, c)
    totals = jnp.sum(f, axis=2)                          # (L, nchunk) f32
    totals = jnp.rint(totals).astype(jnp.int32)
    carry = jnp.cumsum(totals, axis=1) - totals          # exclusive, int32
    return (within + carry[:, :, None]).reshape(l, n)


# ---------------------------------------------------------------------------
# Chunk-list construction (XLA, small arrays only).
# ---------------------------------------------------------------------------


def _chunk_capacity(n: int, w: int, rb: int) -> int:
    """Static worst-case chunk count: every member block contributes <= 2
    chunks per side (consecutive destinations span <= 2 output blocks),
    plus one identity chunk per covered block."""
    member_blocks = n // rb + w          # disjoint windows: sum <= T + W
    return 4 * member_blocks + n // rb


def build_partition_chunks(ps, lc, cw, active, cl, cr, cl_ps, cr_ps,
                           *, n: int, rb: int):
    """Chunk list for ``apply_partition`` from the wave's member windows.

    ps, lc, cw : (W,) int32 — member window start / left row count / width
    active     : (W,) bool — member is valid AND sortable this wave
    cl, cr     : (N,) int32 exclusive cumsums of the left/right row flags
    cl_ps, cr_ps : (W,) int32 — cl/cr gathered at each member's ``ps``

    Returns (ot, it, kind, total, covered):
      ot/it  : (Tc,) int32 output/source row-block per chunk (ot is the
               RAW sort key; invalid chunks carry ot = T+1 and sort last)
      kind   : (Tc,) int32 — 0 identity (unmoved rows), 1 moving rows,
               2 inert (contributes nothing)
      total  : () int32 number of valid chunks (after the ot sort the
               first ``total`` entries are the live ones)
      covered: (T,) bool — row blocks overlapped by any active window
               (rows outside keep their original values)
    """
    w = ps.shape[0]
    t_blocks = n // rb
    cap_m = t_blocks + w                        # member-block walk length
    ps = jnp.where(active, ps, 0)
    cw_a = jnp.where(active, cw, 0)
    lc = jnp.where(active, lc, 0)
    t0 = ps // rb
    t1 = jnp.where(active, (ps + jnp.maximum(cw_a, 1) - 1) // rb, 0)
    nblk = jnp.where(active, t1 - t0 + 1, 0)

    # --- covered row blocks (interval union via diff trick)
    act_i = active.astype(jnp.int32)
    cov_d = jnp.zeros(t_blocks + 1, jnp.int32) \
        .at[jnp.where(active, t0, t_blocks + 7)].add(act_i, mode="drop") \
        .at[jnp.where(active, t1 + 1, t_blocks + 7)].add(-act_i,
                                                         mode="drop")
    covered = jnp.cumsum(cov_d[:t_blocks]) > 0

    # --- walk over (member, source block) pairs (the _segment_hists
    # idiom).  Active members sit at ARBITRARY wave positions (top-k
    # order), so the walk runs over their COMPACTED ranks and maps rank
    # back to the member index through a scatter-built inverse.
    iota_w = jnp.arange(w, dtype=jnp.int32)
    rank = jnp.cumsum(act_i) - act_i                    # rank of actives
    n_act = jnp.sum(act_i)
    inv = jnp.zeros(w, jnp.int32).at[
        jnp.where(active, rank, w + 7)].set(iota_w, mode="drop")
    nblk_c = jnp.where(iota_w < n_act, nblk[inv], 0)
    t0_c = t0[inv]
    off = jnp.cumsum(nblk_c)
    starts = (off - nblk_c).astype(jnp.int32)
    total_m = off[w - 1]
    tpos = jnp.arange(cap_m, dtype=jnp.int32)
    started = jnp.zeros(cap_m, jnp.int32).at[starts].add(
        (iota_w < n_act).astype(jnp.int32), mode="drop")
    rnk = jnp.clip(jnp.cumsum(started) - 1, 0, w - 1)
    mem = inv[rnk]
    live = tpos < total_m
    blk = jnp.where(live, t0_c[rnk] + (tpos - starts[rnk]), 0)

    # block-boundary cumsum values (cl/cr at every block START; a member
    # window's final block always takes the side_total branch below, so
    # the exclusive tail is never consulted past the last boundary)
    cl_t = jnp.concatenate([cl[::rb], cl[-1:]])
    cr_t = jnp.concatenate([cr[::rb], cr[-1:]])

    def side_chunks(cum_t, cum_ps, base, side_total):
        """Per (member, block) chunk pair for one side.  ``base`` is the
        side's destination window start per member; ``side_total`` its
        row count.  Returns (ot_a, ot_b, it, count_a_valid, b_valid)."""
        m = mem
        lo_blk = jnp.maximum(blk * rb, ps[m])
        hi_blk = jnp.minimum((blk + 1) * rb, ps[m] + cw_a[m])
        # ranks of this block's side rows within the member window
        a = jnp.where(lo_blk <= ps[m], 0,
                      cum_t[jnp.minimum(blk, t_blocks)] - cum_ps[m])
        b_end = jnp.where(hi_blk >= ps[m] + cw_a[m], side_total[m],
                          cum_t[jnp.minimum(blk + 1, t_blocks)] - cum_ps[m])
        cnt = jnp.maximum(b_end - a, 0)
        has = live & active[m] & (cnt > 0)
        d0 = base[m] + a
        d1 = base[m] + b_end - 1
        o0 = d0 // rb
        o1 = d1 // rb
        oob = jnp.int32(t_blocks + 1)
        ot_a = jnp.where(has, o0, oob)
        ot_b = jnp.where(has & (o1 != o0), o1, oob)
        return ot_a, ot_b

    left_total = lc
    right_total = cw_a - lc
    la, lb = side_chunks(cl_t, cl_ps, ps, left_total)
    ra, rb_ = side_chunks(cr_t, cr_ps, ps + lc, right_total)

    # --- identity chunks: one per covered block
    ident_ot = jnp.where(covered, jnp.arange(t_blocks, dtype=jnp.int32),
                         t_blocks + 1)

    oob = jnp.int32(t_blocks + 1)
    ot = jnp.concatenate([la, lb, ra, rb_, ident_ot])
    it = jnp.concatenate([blk, blk, blk, blk,
                          jnp.arange(t_blocks, dtype=jnp.int32)])
    kind = jnp.concatenate([
        jnp.ones(4 * cap_m, jnp.int32),
        jnp.zeros(t_blocks, jnp.int32)])
    kind = jnp.where(ot >= oob, 2, kind)
    it = jnp.where(ot >= oob, 0, it)
    # group by output block (accumulation requires same-ot contiguity);
    # invalid chunks (ot = T+1) sort to the tail.  The 3-key sort also
    # makes duplicate (ot, it, kind) triples adjacent: two ADJACENT
    # windows can emit the same (source block -> output block) pair, and
    # the kernel's destination mask would count those rows twice — the
    # duplicate is neutralized to kind=2 (inert)
    ot_s, it_s, kind_s = lax.sort([ot, it, kind], num_keys=3,
                                  is_stable=True)
    dup = jnp.concatenate([
        jnp.zeros(1, bool),
        (ot_s[1:] == ot_s[:-1]) & (it_s[1:] == it_s[:-1])
        & (kind_s[1:] == kind_s[:-1])])
    kind_s = jnp.where(dup, 2, kind_s)
    total = jnp.sum(ot_s < oob, dtype=jnp.int32)
    # clamp tail chunks onto the LAST block: they follow any real chunks
    # for that block (same sort key ordering), so the first-visit init
    # can never wipe accumulated state; kind=2 keeps them inert
    ot_s = jnp.minimum(ot_s, t_blocks - 1)
    return ot_s, it_s, kind_s, total, covered


# ---------------------------------------------------------------------------
# The permute kernel.
# ---------------------------------------------------------------------------


def _byte_planes(fw: int):
    """Number of bf16 transport planes: 4 per packed bin word + 12 for
    the three bitcast f32 weight channels + 3 for rid (< 2^24) + 2 for
    lid (< 2^16)."""
    return 4 * fw + 12 + 3 + 2


def _permute_kernel(ot_ref, it_ref, kind_ref, bins_ref, wbits_ref, rid_ref,
                    lid_ref, dest_ref, mvd_ref, out_ref, *, rb: int,
                    fw: int):
    t = pl.program_id(0)
    ot = ot_ref[t]
    prev = ot_ref[jnp.maximum(t - 1, 0)]
    first = (t == 0) | (ot != prev)

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    kind = kind_ref[t]

    @pl.when(kind < 2)
    def _compute():
        # rows ride the lane axis throughout, as (1, rb) operands: Mosaic
        # refuses the 1-D form (XLA tiles a 1-D s32[N] by 1024, this
        # block is rb), and the transposed one-hot below needs no
        # lane->sublane relayout of the destinations
        dest = dest_ref[...]                    # (1, rb) int32 global dests
        mvd = mvd_ref[...] != 0                 # (1, rb) row moved this wave
        base = ot * rb
        sel = (dest >= base) & (dest < base + rb)
        # identity chunks (kind 0) carry the unmoved rows, moving chunks
        # (kind 1) the moved ones; compared as integers because Mosaic
        # cannot legalize arith.select on i1 vectors
        sel &= mvd.astype(jnp.int32) == kind
        d_local = jnp.where(sel, dest - base, -1)
        iota_d = lax.broadcasted_iota(jnp.int32, (rb, rb), 0)
        oh_t = (iota_d == d_local).astype(jnp.bfloat16)  # (rb dst, rb src)
        planes = []
        for wd in range(fw):
            word = bins_ref[wd:wd + 1, :]
            for s in range(4):
                planes.append((word >> (8 * s)) & 0xFF)
        for c in range(3):            # f32 bit patterns, bitcast by the
            word = wbits_ref[c:c + 1, :]                     # caller
            for s in range(4):
                planes.append((word >> (8 * s)) & 0xFF)
        rid = rid_ref[...]
        for s in range(3):
            planes.append((rid >> (8 * s)) & 0xFF)
        lid = lid_ref[...]
        for s in range(2):
            planes.append((lid >> (8 * s)) & 0xFF)
        a = jnp.concatenate(planes, axis=0) \
            .astype(jnp.bfloat16)                      # (P, rb), 0..255
        # one nonzero product per output element: bf16 transports each
        # byte exactly; accumulation stays in integer-exact range
        part = lax.dot_general(a, oh_t, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        out_ref[0, :, :] += part.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rb", "interpret"))
def _apply_partition_call(ot, it, kind, bins_p, w_bits, rid_p, lid_p, dest,
                          mvd, *, rb: int, interpret: bool = False):
    fw, n = bins_p.shape
    t_blocks = n // rb
    p = _byte_planes(fw)
    grid = (ot.shape[0],)
    row_spec = pl.BlockSpec((1, rb), lambda t, o, i, k: (0, i[t]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((fw, rb), lambda t, o, i, k: (0, i[t])),
            pl.BlockSpec((3, rb), lambda t, o, i, k: (0, i[t])),
            row_spec, row_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((1, p, rb), lambda t, o, i, k: (o[t], 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_permute_kernel, rb=rb, fw=fw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_blocks, p, rb), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="apply_partition_permute",
    )(ot, it, kind, bins_p, w_bits,
      *(v[None, :] for v in (rid_p, lid_p, dest, mvd)))
    return out


def _recombine(out_planes, covered, bins_p, w_p, rid_p, lid_p, *, rb: int):
    """Byte planes (T, P, rb) -> permuted payload; rows of uncovered
    blocks keep their original values."""
    fw, n = bins_p.shape
    planes_i = jnp.rint(out_planes.astype(jnp.float32)).astype(jnp.int32)

    def word(p0):
        b = planes_i[:, p0:p0 + 4, :]              # (T, 4, rb)
        v = (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24))
        return v.reshape(n)

    cov_row = jnp.repeat(covered, rb)
    new_bins = jnp.stack([word(4 * wd) for wd in range(fw)])
    new_w = jax.lax.bitcast_convert_type(
        jnp.stack([word(4 * fw + 4 * c) for c in range(3)]), jnp.float32)
    o = 4 * fw + 12
    b = planes_i[:, o:o + 3, :]
    new_rid = (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16)).reshape(n)
    b = planes_i[:, o + 3:o + 5, :]
    new_lid = (b[:, 0] | (b[:, 1] << 8)).reshape(n)
    bins_o = jnp.where(cov_row[None, :], new_bins, bins_p)
    w_o = jnp.where(cov_row[None, :], new_w, w_p)
    rid_o = jnp.where(cov_row, new_rid, rid_p)
    lid_o = jnp.where(cov_row, new_lid, lid_p)
    return bins_o, w_o, rid_o, lid_o


def apply_partition(bins_p, w_p, rid_p, lid_p, dest, mvd, ps, lc, cw,
                    active, cl, cr, cl_ps, cr_ps, *, row_block: int = 512,
                    interpret: bool = False):
    """Move every row to ``dest`` (a permutation of [0, N)); rows outside
    the active member windows are untouched.  See the module docstring
    for the contract; grid-size buckets mirror ``_segment_hists`` so late
    small-window waves don't pay a full-length chunk walk."""
    fw, n = bins_p.shape
    rb = partition_row_block(n, row_block)
    w = ps.shape[0]
    w_bits = jax.lax.bitcast_convert_type(w_p, jnp.int32)
    ot, it, kind, total, covered = build_partition_chunks(
        ps, lc, cw, active, cl, cr, cl_ps, cr_ps, n=n, rb=rb)
    cap = ot.shape[0]
    sizes = []
    tcap = cap
    floor = max(2 * w, 8)
    while tcap > floor:
        sizes.append(tcap)
        tcap = tcap // 2
    sizes.append(max(floor, tcap))

    def make_branch(ti):
        def branch(ot, it, kind, bins_p, w_bits, rid_p, lid_p, dest, mvd):
            return _apply_partition_call(
                ot[:ti], it[:ti], kind[:ti], bins_p, w_bits, rid_p, lid_p,
                dest, mvd, rb=rb, interpret=interpret)
        return branch

    sz = jnp.asarray(sizes, jnp.int32)
    idx = jnp.maximum(jnp.sum(sz >= total) - 1, 0)
    out = lax.switch(idx, [make_branch(t) for t in sizes], ot, it, kind,
                     bins_p, w_bits, rid_p, lid_p, dest, mvd)
    return _recombine(out, covered, bins_p, w_p, rid_p, lid_p, rb=rb)


def partition_ineligible_reason(n: int, m_slots: int,
                                open_levels: int) -> Optional[str]:
    """Why the partition kernel cannot serve this wave config (None =
    eligible).  ``m_slots`` is the learner's node-slot count M (lid
    values travel as 2 byte planes)."""
    if n > MAX_PARTITION_ROWS:
        return f"{n} rows > 2^24 (rank planes/rid bytes are 24-bit)"
    if m_slots > MAX_PARTITION_SLOTS:
        return f"{m_slots} node slots > 2^16 (lid travels as 2 bytes)"
    if open_levels > 0:
        return "level-wise opening defers multi-level keys (sort only)"
    return None


def partition_transient_bytes(n: int, f_pad: int) -> int:
    """Byte-plane transient of one partition pass (the analogue of the
    sort path's double-buffered operands) for the wave byte budget."""
    return _byte_planes(f_pad // 4) * n * 2
