"""Per-row questions about edge slots, on the device, for the checks.

A check asks of every vertex whether some slot of its row passes a test
(the slot to its parent, a neighbour one level up). ``pack`` folds the
per-lane answers of a block of slots into lane words, 32 lanes to a
``uint32``; ``row_any`` ORs them over each CSR row with a segmented
doubling scan (rows are contiguous runs of slots, so a row's OR is the
scan read out at its last slot): no scatter, ``ceil(log2(longest row))``
whole-array steps. Written apart from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WORD = 32


def pack(flags: jnp.ndarray) -> jnp.ndarray:
    """bool[s, R] -> uint32[s, ceil(R / 32)], lane r at bit r % 32 of
    word r // 32."""
    s, lanes = flags.shape
    w = -(-lanes // WORD)
    padded = jnp.pad(flags, ((0, 0), (0, w * WORD - lanes)))
    bits = padded.astype(jnp.uint32).reshape(s, w, WORD)
    return (bits << jnp.arange(WORD, dtype=jnp.uint32)).sum(
        axis=-1, dtype=jnp.uint32)


def unpack(words: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """uint32[n, W] -> bool[n, lanes]."""
    lane = jnp.arange(lanes)
    return ((words[:, lane // WORD] >> (lane % WORD).astype(jnp.uint32))
            & 1).astype(jnp.bool_)


def row_any(words: jnp.ndarray, row_ptr: jnp.ndarray,
            src_idx: jnp.ndarray) -> jnp.ndarray:
    """uint32[m, W] slot words -> uint32[n, W], the OR over each row's
    slots (0 for a row without slots)."""
    m = words.shape[0]
    x = words.T                                           # [W, m]
    back = jnp.arange(m, dtype=jnp.int32) - row_ptr[src_idx]
    deg = row_ptr[1:] - row_ptr[:-1]

    def double(c):
        step, x = c
        return 2 * step, jnp.where(back >= step,
                                   x | jnp.roll(x, step, axis=-1), x)

    _, x = jax.lax.while_loop(lambda c: c[0] < jnp.max(deg), double,
                              (jnp.int32(1), x))
    last = jnp.clip(row_ptr[1:] - 1, 0, m - 1)
    return jnp.where(deg[:, None] > 0, x[:, last].T, jnp.uint32(0))
