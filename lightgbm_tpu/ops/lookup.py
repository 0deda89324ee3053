"""Per-row small-table lookups as MXU one-hot contractions.

On TPU an XLA gather of 1M rows from a small table costs ~5-8 ms (the
gather unit serializes element loads) while the equivalent one-hot matmul
runs in ~0.5 ms (round-5 chip reading).  Every per-row
``table[leaf_id]``-style lookup in the training path routes through here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _pad_table(table: jax.Array) -> jax.Array:
    m = table.shape[-1]
    m_pad = max(128, ((m + 127) // 128) * 128)
    if m_pad != m:
        pad = [(0, 0)] * (table.ndim - 1) + [(0, m_pad - m)]
        table = jnp.pad(table, pad)
    return table


def lookup_f32(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for f32 ``table (M,)`` / int ``idx (N,)`` — BIT-EXACT
    via byte planes: the f32 bit patterns are split into 4 bytes (each <=
    255, exact in bf16), selected with ONE bf16 one-hot matmul accumulating
    in f32 (a single nonzero term per row, so each byte is exact), and
    reassembled by bit ops.  An f32 HIGHEST-precision one-hot dot
    materializes the (N, M) one-hot at f32 and runs 3x passes (~8 ms/M
    rows); this runs in ~0.5 ms."""
    bits = _pad_table(table.astype(jnp.float32)).view(jnp.int32)
    planes = jnp.stack([(bits >> (8 * i)) & 0xFF for i in range(4)],
                       axis=1).astype(jnp.bfloat16)          # (M, 4)
    oh = jax.nn.one_hot(idx, planes.shape[0], dtype=jnp.bfloat16)
    b = lax.dot_general(oh, planes, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (N, 4)
    bi = jnp.rint(b).astype(jnp.int32)
    out = bi[:, 0] | (bi[:, 1] << 8) | (bi[:, 2] << 16) | (bi[:, 3] << 24)
    return out.view(jnp.float32)


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
