"""Exact order statistics of non-negative float32 by a counting select.

GOSS (``boosting/gbdt.py::goss_masks``) needs two numbers an iteration:
the k-th largest ``|g*h|`` and the k-th smallest uniform draw. A sort, or
``lax.top_k`` at k in the millions (which the TPU lowers to a sort), orders
every row to find them; one order statistic needs no order.

A non-negative float32 (``+0.0`` up to ``+inf``, denormals included) read
as int32 is ordered as the value is, so the k-th largest is the largest
bit pattern ``t`` with ``count(bits >= t) >= k``. ``t`` is built from bit
30 down (the sign bit is never set), ``_BITS`` bits a pass: each pass
counts the rows at or above every candidate digit in one fused read of
the rows, and the digit is the number of candidates that still hold k
rows (the counts fall as the candidate rises). The result is the order
statistic itself, bit for bit, and the cost does not depend on k.

Counts are int32 sums over the array they are given: under ``shard_map``
each shard selects among its own rows and no collective appears.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# Bits settled a pass: 2^_BITS - 1 compares a row against one read of it.
# On a v5e at 57,503,744 rows one select took 10.2 ms at one bit a pass
# (31 reads), 5.6 at two, 4.1 at three (11 reads), 4.5 at four, 7.3 at
# five; `lax.top_k` at k = 11.5M took 174 ms (PERF.md §6, PR 27).
_BITS = 3
# (shift, bits) of each pass: bit 30 down to bit 0, the top pass short
_PASSES = tuple((s, min(_BITS, 31 - s))
                for s in range(30 // _BITS * _BITS, -1, -_BITS))
# full reads of the input one select makes
PASSES = len(_PASSES)


def kth_largest(x: jax.Array, k) -> jax.Array:
    """The ``k``-th largest element of ``x`` (1-D, float32, no negative
    value and no ``-0.0``), equal to ``jnp.sort(x)[n - k]`` bit for bit.
    ``k`` may be traced; it is clipped to ``[1, n]``."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    k = jnp.clip(jnp.asarray(k, jnp.int32), 1, x.shape[0])
    t = jnp.int32(0)
    for shift, nbits in _PASSES:
        digit = jnp.int32(0)
        for j in range(1, 1 << nbits):
            at_or_above = jnp.sum(bits >= (t | (j << shift)),
                                  dtype=jnp.int32)
            digit += (at_or_above >= k).astype(jnp.int32)
        t = t | (digit << shift)
    return lax.bitcast_convert_type(t, jnp.float32)


def kth_smallest(x: jax.Array, k) -> jax.Array:
    """The ``k``-th smallest element of ``x``: ``jnp.sort(x)[k - 1]`` bit
    for bit, under ``kth_largest``'s conditions."""
    return kth_largest(x, x.shape[0] + 1 - jnp.asarray(k, jnp.int32))
