"""Pallas TPU kernel: fused multi-leaf histogram construction.

Reference: the CUDA histogram kernel
(src/treelearner/cuda/cuda_histogram_constructor.cu, UNVERIFIED — empty
mount, see SURVEY.md banner) builds per-leaf histograms with shared-memory
atomic adds. TPUs have no fast scatter-atomics; the MXU formulation is

    hist[k, f, b, c] = sum_r [bin(r,f) == b] * [leaf(r) == small_k] * vals[r, c]

One grid step processes a row block: the bin one-hot ``[F*B, R]`` is
generated in VMEM (never staged through HBM — the failure mode of the XLA
einsum formulation) and contracted on the MXU in ONE large
``[F*B, R] x [R, K*C]`` matmul.

The K axis is the TPU-specific trick: packing K candidate leaves' masks
into the matmul N dimension amortizes the MXU's 128-wide N padding, so one
data scan yields K leaf histograms (K*C ≈ 128 → negligible padding waste).
The batched tree grower (learner/serial.py) exploits this by expanding the
top-K leaves per round.

Measured on v5e (1M rows, F=28, B=256): ~23ms/scan at K=8, ~34ms at K=42 —
the floor is the VPU one-hot generation (int32 compares; int8/bf16 vector
compares are unsupported by this target), not the matmul.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hist_kernel(bins_ref, vals_ref, leaf_ref, small_ref, out_ref, *,
                 num_bins: int, n_feat: int, n_leaves: int, n_chan: int,
                 int_mode: bool = False):
    i = pl.program_id(1)      # row-block index (feature block is dim 0)
    # bins stored int8 to halve HBM traffic; wrapped values are restored
    # with & 0xFF after widening (cheap at [F, R])
    bins_blk = bins_ref[...].astype(jnp.int32) & 0xFF    # [F, R]
    vals_blk = vals_ref[...]                             # [C, R]
    lid = leaf_ref[...]                                  # [1, R]
    small = small_ref[...]                               # [K, 1]

    mask = (lid == small).astype(jnp.float32)            # [K, R]
    prod = (mask[:, None, :] * vals_blk[None, :, :]) \
        .reshape(n_leaves * n_chan, -1)
    # int_mode (use_quantized_grad): grad/hess are small integer levels,
    # so the contraction rides the MXU's 2x-rate int8 path with EXACT
    # int32 accumulation (the reference's integer-histogram design,
    # cuda_gradient_discretizer.cu; measured 1.25x/scan on v5e)
    rhs = prod.astype(jnp.int8 if int_mode else jnp.bfloat16)

    # [B*F, R] one-hot in tiled layout (pltpu.repeat tiles the F rows B
    # times: row q corresponds to (b = q // F, f = q % F))
    big = pltpu.repeat(bins_blk, num_bins, axis=0)
    iota_b = (jax.lax.broadcasted_iota(jnp.int32, (n_feat * num_bins, 1),
                                       0) // n_feat)
    onehot = (big == iota_b).astype(jnp.int8 if int_mode
                                    else jnp.bfloat16)

    contrib = jax.lax.dot_general(
        onehot, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=(jnp.int32 if int_mode
                                else jnp.float32))       # [B*F, K*C]

    @pl.when(i == 0)
    def _():
        out_ref[...] = contrib

    @pl.when(i > 0)
    def _():
        out_ref[...] += contrib


def feature_blocks(F: int, num_bins: int) -> Tuple[int, int]:
    """(features a block, blocks) of the kernel's feature grid; their
    product is the padded feature count the one-hot is generated for."""
    F_blk = F if F * num_bins <= 8192 else max(1, 4096 // num_bins)
    return F_blk, (F + F_blk - 1) // F_blk


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "rows_per_block",
                                    "int_mode"))
def multi_leaf_histogram(bins_t: jax.Array, vals_t: jax.Array,
                         leaf_id: jax.Array, small_ids: jax.Array, *,
                         num_bins: int,
                         rows_per_block: int = 2048,
                         int_mode: bool = False) -> jax.Array:
    """Histograms of K leaves in one fused scan (TPU Pallas path).

    Args:
      bins_t: ``[F, n]`` int8 FEATURE-MAJOR binned matrix (transposed once
        at setup so row blocks are lane-contiguous; uint8 values stored
        with int8 wraparound).
      vals_t: ``[C, n]`` float32 channel-major per-row values
        (grad*m, hess*m, count-mask) — bagging masks pre-applied.
      leaf_id: ``[n]`` int32 current leaf of each row.
      small_ids: ``[K]`` int32 leaf ids to histogram (-1 entries match no
        row, giving zero histograms for inactive slots).
      num_bins: static histogram width B.

    Returns:
      ``[K, F, B, C]`` float32.
    """
    F, n = bins_t.shape
    C = vals_t.shape[0]
    K = small_ids.shape[0]
    R = rows_per_block
    assert n % R == 0, f"n={n} must be a multiple of rows_per_block={R}"

    # feature blocking keeps the [B*F_blk, K*C] VMEM accumulator (and the
    # transient one-hot) bounded for wide datasets (MSLR F=136+); at
    # F*B <= 8192 this is a single block, identical to the unblocked
    # form. Blocked (wide-F) layouts use a half-size block: [8192, R]
    # streaming exceeds the 16MB scoped-vmem budget at K*C ~ 96+
    # (measured: 16.25M at F_blk=32, B=256, R=2048 on v5e).
    F_blk, n_fb = feature_blocks(F, num_bins)
    F_pad = n_fb * F_blk
    if F_pad > F:
        bins_t = jnp.concatenate(
            [bins_t, jnp.zeros((F_pad - F, n), bins_t.dtype)])

    kernel = functools.partial(_hist_kernel, num_bins=num_bins,
                               n_feat=F_blk, n_leaves=K, n_chan=C,
                               int_mode=int_mode)
    # NO input_output_aliases here (examined, round 7 — docs/perf.md
    # "Iteration floor"): the [B*F_pad, K*C] accumulator is an
    # output-only carry across the sequential row-block grid, already
    # accumulated in place in VMEM by the @pl.when(i>0) add; no input
    # operand shares its shape/dtype, and threading a caller-supplied
    # zeroed buffer just to alias it would ADD an HBM zero-fill per
    # call — strictly worse than the status quo.
    out = pl.pallas_call(
        kernel,
        grid=(n_fb, n // R),
        in_specs=[
            pl.BlockSpec((F_blk, R), lambda j, i: (j, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, R), lambda j, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, R), lambda j, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((K, 1), lambda j, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((num_bins * F_blk, K * C),
                               lambda j, i: (j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((num_bins * F_pad, K * C),
                                       jnp.int32 if int_mode
                                       else jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * F_pad * num_bins * n * K * C,
            bytes_accessed=bins_t.size + vals_t.size * 4 + leaf_id.size * 4,
            transcendentals=0),
        # the device op's name, pinned: profile readers match it
        name="multi_leaf_histogram",
    )(bins_t, vals_t, leaf_id.reshape(1, n), small_ids.reshape(K, 1))
    if int_mode:
        out = out.astype(jnp.float32)
    # per block j, row q = b * F_blk + f_local
    out = out.reshape(n_fb, num_bins, F_blk, K, C)
    out = out.transpose(3, 0, 2, 1, 4).reshape(K, F_pad, num_bins, C)
    return out[:, :F]


def multi_leaf_histogram_xla(bins: jax.Array, vals: jax.Array,
                             leaf_id: jax.Array, small_ids: jax.Array, *,
                             num_bins: int,
                             rows_per_block: int = 1024,
                             precise: bool = False) -> jax.Array:
    """XLA fallback (CPU tests / non-TPU backends): same contract via the
    einsum-based build_histogram with leaf masks packed into channels.
    ``precise`` keeps grad/hess in float32 (tpu_double_precision_hist)
    instead of the default bfloat16 operands."""
    from .histogram import build_histogram
    K = small_ids.shape[0]
    n, _F = bins.shape
    C = vals.shape[1]
    mask = (leaf_id[:, None] == small_ids[None, :]).astype(vals.dtype)
    packed = (mask[:, :, None] * vals[:, None, :]).reshape(n, K * C)
    hist = build_histogram(bins, packed, num_bins=num_bins,
                           rows_per_block=rows_per_block, precise=precise)
    F, B, _ = hist.shape
    return hist.reshape(F, B, K, C).transpose(2, 0, 1, 3)
