"""Per-row small-table lookups as MXU one-hot contractions.

On a TPU v5e an XLA gather of 10,500,096 rows from a 255-entry table takes
86.5 ms, 8.2 ns a row (ledger, PR 25: `phase_gradients_ms_per_iter` 86.8 in
both cells, the gradients 0.26 of it); ``lookup_f32``'s chunked contraction
over the same rows takes 2.7 ms (my chip run, PR 26: the same metric reads
2.98).  Every per-row ``table[leaf_id]``-style lookup in the training path
routes through here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .histogram import _on_tpu

# one-hot elements of one step of ``lookup_f32``'s chunked contraction:
# 2^17 rows at a table of 256 padded entries, fewer at a wider table
_ONE_HOT_ELEMS = 1 << 25


def _pad_table(table: jax.Array) -> jax.Array:
    m = table.shape[-1]
    m_pad = max(128, ((m + 127) // 128) * 128)
    if m_pad != m:
        pad = [(0, 0)] * (table.ndim - 1) + [(0, m_pad - m)]
        table = jnp.pad(table, pad)
    return table


def lookup_int(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for int32 ``table (M,)`` with |values| < 2^24: the
    contraction runs in f32 (exact for these magnitudes) and rounds back."""
    t = _pad_table(table.astype(jnp.float32))
    oh = jax.nn.one_hot(idx, t.shape[0], dtype=jnp.float32)
    out = lax.dot_general(oh, t, (((1,), (0,)), ((), ())),
                          precision=lax.Precision.HIGHEST)
    return jnp.rint(out).astype(jnp.int32)


def lookup_rows_f32(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for f32 ``table (M, C)`` → ``(N, C)`` one-hot matmul."""
    t = jnp.swapaxes(_pad_table(jnp.swapaxes(
        table.astype(jnp.float32), 0, 1)), 0, 1)
    oh = jax.nn.one_hot(idx, t.shape[0], dtype=jnp.float32)
    return lax.dot_general(oh, t, (((1,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST)


def _planes_lookup(planes: jax.Array, idx: jax.Array) -> jax.Array:
    """One contraction with the ROWS ON THE LANE AXIS: ``planes (4, M) x
    one_hot.T (M, n)`` gives ``(4, n)``, so neither the product nor the
    reassembly touches a lane-sparse ``(n, 4)`` array."""
    oh = (lax.broadcasted_iota(jnp.int32, (planes.shape[1], idx.shape[0]), 0)
          == idx[None, :]).astype(jnp.bfloat16)
    b = lax.dot_general(planes, oh, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    bi = b.astype(jnp.int32)        # one nonzero term a row: whole numbers
    out = bi[0] | (bi[1] << 8) | (bi[2] << 16) | (bi[3] << 24)
    return lax.bitcast_convert_type(out, jnp.float32)


def lookup_f32(table: jax.Array, idx: jax.Array,
               chunk_rows: int | None = None) -> jax.Array:
    """``table[idx]`` for f32 ``table (M,)`` / int ``idx (N,)`` — BIT-EXACT
    via byte planes: the f32 bit patterns are split into 4 bytes (each <=
    255, exact in bf16), selected with ONE bf16 one-hot matmul accumulating
    in f32 (a single nonzero term per row, so each byte is exact), and
    reassembled by bit ops.

    Over row chunks, so the ``(M_pad, rows)`` one-hot is bounded whatever N
    is: 64 MiB if the compiler were to write it out (for a v5e it fuses the
    compare into the convolution and writes none).  The chunks are 1-D
    ``dynamic_slice``s of ``idx``, written back by ``dynamic_update_slice``:
    a ``lax.map`` over a ``(chunks, rows)`` reshape forces a relayout of
    both row arrays (``T(1024)`` to ``T(8,128)`` tiles), which the compiler
    does in unscoped loops of its own (2.8 ms against 4.8 at 10,500,096
    rows, and 8.3 with the rows on the sublanes; my chip run, PR 26).  The
    last chunk starts at ``N - rows``: where N is no multiple of the chunk
    it reads some rows twice and writes them the same values."""
    bits = lax.bitcast_convert_type(
        _pad_table(table.astype(jnp.float32)), jnp.int32)
    planes = jnp.stack([(bits >> (8 * i)) & 0xFF for i in range(4)],
                       axis=0).astype(jnp.bfloat16)          # (4, M_pad)
    n = idx.shape[0]
    if chunk_rows is None:
        # whole 1,024-row tiles of the 1-D row layout
        chunk_rows = max(1, _ONE_HOT_ELEMS // planes.shape[1] // 1024) * 1024
    if n <= chunk_rows:
        return _planes_lookup(planes, idx)

    def step(i, out):
        start = jnp.minimum(i * chunk_rows, n - chunk_rows)
        vals = _planes_lookup(
            planes, lax.dynamic_slice(idx, (start,), (chunk_rows,)))
        return lax.dynamic_update_slice(out, vals, (start,))

    return lax.fori_loop(0, -(-n // chunk_rows), step,
                         jnp.zeros(n, jnp.float32))


def lookup_leaf_values(table: jax.Array, idx: jax.Array,
                       row_sharded: bool = False) -> jax.Array:
    """The training-score update's ``leaf_out[leaf_id]`` — its only reader.
    On a TPU the chunked MXU contraction; elsewhere the plain gather, which
    is cheaper there.  Bit-identical either way, so the backend the arrays
    live on picks the path and nothing else does.

    A ``row_sharded`` ``idx`` (the sharded learners' ``leaf_id``) keeps the
    gather on a TPU too: the contraction's chunks slice the row axis, which
    the partitioner may answer with collectives, and no benchmark cell nor
    the CPU tests' pinned collective order (`tests/test_parallel.py`,
    `test_spmd.py`: they trace the gather) can judge that program."""
    if _on_tpu() and not row_sharded:
        return lookup_f32(table, idx)
    return jnp.take(table, idx)
