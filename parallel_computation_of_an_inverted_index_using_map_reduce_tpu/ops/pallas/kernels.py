"""Pallas TPU kernels for the device engine's hot ops.

Two kernels, both exact drop-ins for their XLA counterparts:

- :func:`unique_mask_count` — the reduce phase's per-(term, doc) dedup
  (the reference's linear dictionary scan, main.c:172-187) as ONE fused
  pass over the sorted key array: boundary diff + validity mask +
  global unique count.  XLA expresses this as three kernels (pad-shift
  compare, elementwise and, reduce); here it is a single VMEM sweep
  using the sequential-grid carry pattern — block ``i+1`` sees block
  ``i``'s last key through SMEM scratch, which TPU's in-order grid
  execution makes race-free.

- :func:`bucket_histogram` — per-partition pair counts used by
  utils/stats.py to measure shuffle skew per run: the reference's
  first-letter partition is ~1000x imbalanced on real text while the
  engine's hash buckets are near-uniform (SURVEY.md §2.3).  Bucket
  counts are small (mesh size or 26 letters), so each block reduces
  with a static unrolled compare loop on the VPU; counts accumulate in
  SMEM across the sequential grid.

Both run compiled on TPU and in interpreter mode elsewhere (tests force
``interpret=True`` on the CPU backend via :func:`_should_interpret`).

Measured on a v5e in round 3 (2^20 keys, device-side dispatch loops;
not in the ledger): the fused kernel ran ~18 us vs ~14 us for the XLA
three-kernel path — XLA's own fusion already wins here, and the
sequential-grid carry serializes what XLA parallelizes.  The kernel is
kept (a) as the measured datapoint behind that conclusion and (b) as
the fused-sweep pattern the playbook needs at sizes where the extra
pass over HBM dominates; ``MRI_TPU_PALLAS=off`` selects XLA everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One grid block: 64 sublanes x 128 lanes of int32 = 32 KiB of VMEM.
_BLOCK_ROWS = 64
_LANES = 128
_BLOCK = _BLOCK_ROWS * _LANES
BLOCK = _BLOCK  # public: callers pad array lengths to a multiple of this


def _should_interpret() -> bool:
    """Compiled on real TPU; interpreted on CPU (tests, dry runs)."""
    return jax.default_backend() != "tpu"


def supports(n: int) -> bool:
    """True if an ``n``-element array fits the kernels' block layout."""
    return n >= _BLOCK and n % _BLOCK == 0


# ---------------------------------------------------------------------------
# unique_mask_count
# ---------------------------------------------------------------------------


def _unique_kernel(keys_ref, limit_ref, mask_ref, count_ref, carry_ref, *,
                   block_rows: int):
    i = pl.program_id(0)
    k = keys_ref[:]  # (R, 128) int32, ascending across the flattened array

    @pl.when(i == 0)
    def _init():
        # packed keys are >= 0, so k[0,0] - 1 cannot wrap
        carry_ref[0] = k[0, 0] - 1
        count_ref[0, 0] = 0

    # shifted[r, l] = previous element in flattened row-major order,
    # built from full-block rolls (Mosaic-friendly: no narrow concats).
    # roll along lanes puts k[r, 127] at (r, 0) — wrong row; a second
    # roll along sublanes fixes column 0, and (0, 0) comes from the
    # cross-block carry.
    rolled_lanes = pltpu.roll(k, shift=1, axis=1)
    rolled_both = pltpu.roll(rolled_lanes, shift=1, axis=0)
    row = jax.lax.broadcasted_iota(jnp.int32, (block_rows, _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_rows, _LANES), 1)
    shifted = jnp.where(col == 0, rolled_both, rolled_lanes)
    shifted = jnp.where((col == 0) & (row == 0), carry_ref[0], shifted)

    mask = (k != shifted) & (k < limit_ref[0, 0])
    mask_ref[:] = mask.astype(jnp.int32)
    count_ref[0, 0] += jnp.sum(mask.astype(jnp.int32))
    carry_ref[0] = k[block_rows - 1, _LANES - 1]


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def _unique_call(keys2d, limit, *, interpret: bool,
                 block_rows: int = _BLOCK_ROWS):
    if keys2d.shape[0] % block_rows:
        raise ValueError(
            f"{keys2d.shape[0]} rows not divisible by block_rows {block_rows}")
    grid = keys2d.shape[0] // block_rows
    mask, count = pl.pallas_call(
        functools.partial(_unique_kernel, block_rows=block_rows),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(keys2d.shape, jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(keys2d, limit)
    return mask, count


def unique_mask_count(sorted_keys, valid_limit: int):
    """Fused first-occurrence mask + unique count over ascending keys.

    Equivalent to ``first_occurrence_mask(k) & (k < valid_limit)`` plus
    the mask's sum (ops/segment.py), in one pass.  Returns
    ``(mask bool (n,), count int32 scalar)``.  Requires
    :func:`supports`\\ ``(n)``; callers fall back to the XLA path
    otherwise.
    """
    n = sorted_keys.shape[0]
    if not supports(n):
        raise ValueError(f"unsupported size {n}; check supports() first")
    keys2d = sorted_keys.reshape(n // _LANES, _LANES)
    limit = jnp.full((1, 1), valid_limit, jnp.int32)
    mask, count = _unique_call(keys2d, limit, interpret=_should_interpret())
    return mask.reshape(n).astype(bool), count[0, 0]


# ---------------------------------------------------------------------------
# bucket_histogram
# ---------------------------------------------------------------------------


def _hist_kernel(vals_ref, counts_ref, *, num_buckets: int):
    i = pl.program_id(0)
    v = vals_ref[:]  # (R, 128) int32

    @pl.when(i == 0)
    def _init():
        for b in range(num_buckets):
            counts_ref[0, b] = 0

    # static unrolled compare loop: num_buckets is small (mesh size / 26)
    for b in range(num_buckets):
        counts_ref[0, b] += jnp.sum((v == b).astype(jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("num_buckets", "interpret", "block_rows"))
def _hist_call(vals2d, *, num_buckets: int, interpret: bool,
               block_rows: int = _BLOCK_ROWS):
    if vals2d.shape[0] % block_rows:
        raise ValueError(
            f"{vals2d.shape[0]} rows not divisible by block_rows {block_rows}")
    grid = vals2d.shape[0] // block_rows
    return pl.pallas_call(
        functools.partial(_hist_kernel, num_buckets=num_buckets),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, num_buckets), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, num_buckets), jnp.int32),
        interpret=interpret,
    )(vals2d)


def bucket_histogram(values, num_buckets: int):
    """Count occurrences of each bucket id in ``values``.

    ``values`` outside ``[0, num_buckets)`` (e.g. padding) are ignored.
    Equivalent to ``jnp.bincount(values, length=num_buckets)`` for
    in-range values; int32 (num_buckets,).  Requires
    :func:`supports`\\ ``(len(values))`` and ``num_buckets <= 128``.
    """
    n = values.shape[0]
    if not supports(n):
        raise ValueError(f"unsupported size {n}; check supports() first")
    if not 1 <= num_buckets <= 128:
        raise ValueError(f"num_buckets must be in [1, 128], got {num_buckets}")
    vals2d = values.reshape(n // _LANES, _LANES).astype(jnp.int32)
    counts = _hist_call(
        vals2d, num_buckets=num_buckets, interpret=_should_interpret())
    return counts[0]
