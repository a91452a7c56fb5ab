"""Pallas TPU kernels: blocked Vandermonde-Gram moments + fused fit report.

TPU-native adaptation of the paper's CUDA moment kernel (DESIGN.md §2):

* For batches, the paper's per-thread partial power sums become a *single
  MXU matmul* per data tile. With W = [V | y] (rows = powers of x, then
  y), the product G = (W ⊙ w) Wᵀ simultaneously yields the Hankel/Gram
  matrix, the moment vector Vᵀy, Σwy² and Σw — every sufficient statistic
  of the fit.
* Grid streams (batch, n-block) tiles HBM→VMEM; the (128, 128) accumulator
  tile stays VMEM-resident across the n-block grid dimension (constant
  index_map), mirroring the shared-memory block reduction on GPU.
* Power rows are built by iterated multiply (no transcendental `pow`),
  matching the paper's "matricized" construction.

Four kernels live here:

``moments_extended``          one series per (128, block_n) MXU tile (the
                              original layout; rows degree+2..127 are zero),
                              ROW_BLOCK series per grid step.
``moments_flat``              ONE (n,) series read in place, with no matmul:
                              the 3·degree+3 power sums Σw·x^k, Σw·x^k·y and
                              Σw·y² as VPU products over FLAT_BLOCK_N-point
                              blocks, one (8, 128) vreg of 1,024 live points
                              at a time; the Hankel Gram A[j, k] = S_{j+k} is
                              assembled from them.  No weights stream when
                              unweighted, no padded copy; only the ragged
                              last block is masked.  A 128-row MXU tile would
                              spend 2·128² FLOP a point on its degree+2 live
                              rows (25× the work at degree 3).
``moments_packed_extended``   P = 128 // (degree+2) series per tile — the
                              packed layout below.
``fused_report_sums``         one streamed pass computing everything
                              ``core.fit.fit_report`` needs (SSE, R) without
                              materializing fitted/residual arrays in HBM.

Packed layout (the perf-critical path for batched fits)
-------------------------------------------------------
The MXU always multiplies full (128, block_n) × (block_n, 128) tiles, so
with one series per tile a degree-3 fit (K = degree+2 = 5 live rows) wastes
123/128 ≈ 96% of every matmul on zeros. Packing P = 128 // K independent
series into the sublane dimension turns that padding into useful work:

      sublane 0   ┌ 1  1  1 … ┐   series 0, power 0
              1   │ x₀ row    │   series 0, power 1..m
              …   │ …         │
              K-1 │ y₀ row    │   series 0, response
              K   │ 1  1  1 … │   series 1, power 0
              …   │ …         │   …
          P·K-1   │ y_{P-1}   │   series P-1, response
          P·K..127└ 0 zeros   ┘   remainder rows (128 mod K)

G = (W ⊙ w) Wᵀ then contains each series' (K × K) extended Gram as the
p-th diagonal block G[pK:(p+1)K, pK:(p+1)K]; off-diagonal blocks are
cross-series products we simply never read. Per *fit* the MXU work drops
from 2·128²·n to 2·128²·n/P FLOPs — 25× at degree 3, 14× at degree 7,
9× at degree 12. Tail series (batch not divisible by P) ride in with
weight 0, so they contribute exact zeros and are sliced away by ops.py.

VMEM footprint of the packed tile (f32 accumulate, block_n = 4096):
  x/y/w input tiles   3 · P·block_n · 4 B   ≈ 1.2 MB  (P = 25)
  W and (W ⊙ w)       2 · 128·block_n · 4 B ≈ 4.2 MB
  G accumulator       128² · 4 B            ≈ 65 KB   (×2 if compensated)
  total ≈ 5.5 MB — comfortably inside the ~16 MB/core budget; halve
  block_n for the compensated path if other buffers share the core.

Path selection (see ``ops.moments``): packed when the batch has ≥ 2 series
and P ≥ 2 (i.e. degree ≤ 62); plain for single series or huge degrees, a
lone (n,) series on ``moments_flat``; the pure-jnp ``core.gram_moments``
remains the non-kernel reference path.

Compensated accumulation
------------------------
Skala (arXiv:1802.07591) shows naive monomial power sums lose precision at
exactly the large-n scale the paper targets. ``compensated=True`` keeps a
second VMEM-resident tile carrying a Kahan running-error term: each block's
contribution is corrected by the error of the previous addition, making the
cross-block reduction error O(1) in the number of blocks instead of O(nblk).
Costs one extra accumulator tile and 3 extra VPU adds per block — invisible
next to the block's own work.

Double buffering (``nbuf >= 2``)
--------------------------------
The grid-streamed form above leaves the HBM→VMEM pipelining entirely to the
Mosaic pipeliner. ``moments_packed_extended(..., nbuf=2)`` instead runs ONE
grid step per group and drives the n-block loop in-kernel over an explicit
``nbuf``-slot VMEM scratch ring: the DMA for block k+1 is started *before*
the matmul on block k, so the MXU never waits on HBM as long as one block's
compute covers one block's transfer (true for every block_n ≥ 1024 at the
moment pass's arithmetic intensity). Inputs stay in ``ANY`` (HBM) memory
space; per-slot DMA semaphores sequence the ring. The per-block update and
accumulation order are IDENTICAL to the grid-streamed kernel (shared
``_packed_tile_update``), so the two paths are bit-equal by construction —
asserted in tests. Pick ``block_n`` with ``repro.kernels.tune``
(one-shot sweep cached per (degree, dtype, backend)).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

K_PAD = 128          # fixed row count: degree + 2 <= 128
DEFAULT_BLOCK_N = 4096
# The lone-series pass (moments_flat) walks blocks of FLAT_BLOCK_N points
# (1 MB of f32 a stream, double-buffered) one SLAB, one f32 vreg of
# 8 × 128 points, at a time, FLAT_UNROLL slabs a loop iteration.
FLAT_BLOCK_N = 1 << 18
SLAB_SHAPE = (8, 128)
SLAB = SLAB_SHAPE[0] * SLAB_SHAPE[1]
FLAT_UNROLL = 8
# series per grid step of the plain and fused-report kernels: a data block's
# second-to-last dim must be a multiple of 8 or the full batch (TPU tiling)
ROW_BLOCK = 8
# Precision of the f32 Gram products on the MXU (the batched and packed
# kernels; moments_flat multiplies in f32 on the VPU).  DEFAULT rounds both
# operands to bf16 once, and the error that leaves in a fit shrinks as the
# series grows; HIGHEST (six bf16 passes) is f32-accurate at about five times
# the MXU time.  Excess SSE over the f64 least-squares fit, degree 3 on TPU
# v5e, DEFAULT / HIGHEST: one series of 2048 points 8.5e-4 / 1.8e-8, of 2^15
# points 3.8e-5 / 2.2e-9, of 2^17 to 2^27 points <= 1.5e-6 / <= 3.8e-7.
DEFAULT_PRECISION_MIN_N = 1 << 17

# index layout of the fused-report sums vector (lane j of the (B, 128) out)
SUM_W, SUM_Y, SUM_YY, SUM_F, SUM_FF, SUM_YF, SUM_SSE, N_SUMS = range(8)


def packing_factor(degree: int) -> int:
    """How many independent series fit in one 128-sublane tile."""
    return K_PAD // (degree + 2)


def flat_sums(degree: int) -> int:
    """Power sums of the lone-series pass: S_0..S_2d, T_0..T_d, Σwy²."""
    return 3 * degree + 3


def gram_precision(n: int) -> jax.lax.Precision:
    """MXU precision of the Gram products for series of n points a call."""
    if n >= DEFAULT_PRECISION_MIN_N:
        return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST


def row_block(b: int) -> int:
    """Rows per grid step of the plain and fused-report kernels for a batch
    of b series: the whole batch below ROW_BLOCK, else ROW_BLOCK.  The
    batch they take must be a multiple of it (pad with zero-weight rows)."""
    return min(b, ROW_BLOCK)


def _checked_row_block(b: int) -> int:
    rb = row_block(b)
    if b % rb:
        raise ValueError(f"batch {b} must be <= {ROW_BLOCK} or a multiple "
                         f"of {ROW_BLOCK} (pad with zero-weight rows)")
    return rb


def _accum_init(i, out_refs):
    """Zero all VMEM accumulator tiles on the first n-block."""
    @pl.when(i == 0)
    def _init():
        for ref in out_refs:
            ref[...] = jnp.zeros_like(ref)


def _accum_add(update, g_ref, c_ref):
    """g += update, optionally Kahan-compensated via the c_ref error tile."""
    if c_ref is None:
        g_ref[...] += update
    else:
        y = update - c_ref[...]
        t = g_ref[...] + y
        c_ref[...] = (t - g_ref[...]) - y
        g_ref[...] = t


def _power_rows(x, y, degree):
    """[x^0, ..., x^degree, y] stacked on a new leading axis."""
    rows = [jnp.ones_like(x)]
    for _ in range(degree):
        rows.append(rows[-1] * x)
    rows.append(y)
    return jnp.stack(rows, axis=0)


def _plain_tile_update(x, y, w, degree: int, accum_dtype, precision):
    """One series' (128, 128) Gram contribution of a (block_n,) slice."""
    # Build W rows by the iterated-multiply power ladder (paper's trick).
    wmat = _power_rows(x, y, degree)                         # (deg+2, bn)
    pad = K_PAD - (degree + 2)
    if pad:
        wmat = jnp.concatenate(
            [wmat, jnp.zeros((pad, wmat.shape[1]), accum_dtype)], axis=0)
    # MXU: (128, bn) @ (bn, 128), f32 accumulation; one side weighted.
    return jax.lax.dot_general(
        wmat * w, wmat, (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=accum_dtype)


def _moments_kernel(x_ref, y_ref, w_ref, g_ref, *maybe_c, degree: int,
                    accum_dtype, precision):
    """One (row-block, n-block) grid step: G[r] += (W_r·w_r) W_rᵀ for each
    series r of the (rb, block_n) tile."""
    c_ref = maybe_c[0] if maybe_c else None
    i = pl.program_id(1)
    _accum_init(i, (g_ref,) + ((c_ref,) if c_ref is not None else ()))

    x = x_ref[...].astype(accum_dtype)   # (rb, block_n)
    y = y_ref[...].astype(accum_dtype)
    w = w_ref[...].astype(accum_dtype)
    updates = [_plain_tile_update(x[r], y[r], w[r], degree, accum_dtype,
                                  precision) for r in range(x.shape[0])]
    _accum_add(jnp.stack(updates), g_ref, c_ref)


def _slab_terms(x, y, w, degree: int):
    """One slab's terms of the lone-series power sums, in the order
    ``moments_flat`` indexes them: w·x^k for k = 0..2·degree, w·x^k·y for
    k = 0..degree, then w·y².  The weights are the ladder's base;
    unweighted (w None) the base is 1, and Σw, which is n, has no term
    (None)."""
    s = [w]
    for _ in range(2 * degree):
        s.append(x if s[-1] is None else s[-1] * x)
    wy = y if w is None else w * y
    t = [wy] + [s[k] * y for k in range(1, degree + 1)]
    return s + t + [wy * y]


def _moments_flat_kernel(*refs, n: int, degree: int, weighted: bool,
                         accum_dtype):
    """One n-block of a lone series: the 3·degree+3 power sums of its
    points below n, added to (8, 128) lane partials that stay in VMEM
    across the grid.  The block is walked one (8, 128) slab (one f32
    vreg, 1,024 points) at a time, so every product is a VPU op on live
    points.  The ragged last block reads past the array's end; on that
    step alone x, y and w are zeroed there (a select, not a product:
    0 · NaN is NaN)."""
    x_ref, y_ref = refs[:2]
    w_ref = refs[2] if weighted else None
    s_ref, *maybe_c = refs[2 + weighted:]
    c_ref = maybe_c[0] if maybe_c else None
    i = pl.program_id(0)
    _accum_init(i, (s_ref,) + ((c_ref,) if c_ref is not None else ()))

    bn = x_ref.shape[0]
    n_slabs = bn // SLAB
    unroll = math.gcd(n_slabs, FLAT_UNROLL)
    lane_index = (jax.lax.broadcasted_iota(jnp.int32, SLAB_SHAPE, 0)
                  * SLAB_SHAPE[1]
                  + jax.lax.broadcasted_iota(jnp.int32, SLAB_SHAPE, 1))
    zero = jnp.zeros((), accum_dtype)

    def slab(ref, r):
        start = pl.multiple_of(r * SLAB, SLAB)
        return ref[pl.ds(start, SLAB)].astype(accum_dtype).reshape(SLAB_SHAPE)

    def block_sums(masked: bool):
        def add_slab(r, acc):
            x, y = slab(x_ref, r), slab(y_ref, r)
            w = None if w_ref is None else slab(w_ref, r)
            if masked:
                live = lane_index < n - i * bn - r * SLAB
                x = jnp.where(live, x, zero)
                y = jnp.where(live, y, zero)
                w = None if w is None else jnp.where(live, w, zero)
            return tuple(a if t is None else a + t
                         for a, t in zip(acc, _slab_terms(x, y, w, degree)))

        def add_slabs(g, acc):
            for k in range(unroll):
                acc = add_slab(g * unroll + k, acc)
            return acc

        init = tuple(jnp.zeros(SLAB_SHAPE, accum_dtype)
                     for _ in range(flat_sums(degree)))
        acc = jax.lax.fori_loop(0, n_slabs // unroll, add_slabs, init)
        _accum_add(jnp.stack(acc), s_ref, c_ref)

    if n % bn == 0:
        block_sums(masked=False)
        return
    last = pl.num_programs(0) - 1

    @pl.when(i < last)
    def _full():
        block_sums(masked=False)

    @pl.when(i == last)
    def _ragged():
        block_sums(masked=True)


def _packed_tile_update(x, y, w, degree: int, accum_dtype, precision):
    """The packed layout's (1, 128, 128) Gram contribution of one
    (P, block_n) tile — the ONE definition both the grid-streamed and the
    double-buffered kernels accumulate, so their results agree bitwise."""
    x = x.astype(accum_dtype)
    y = y.astype(accum_dtype)
    w = w.astype(accum_dtype)
    p, bn = x.shape
    k = degree + 2

    # Series-major rows built one (1, bn) row at a time: row s*K + j holds
    # series s's x^j (its y at j = K-1), so each series owns a contiguous
    # sublane block (diagonal extraction below).  Interleaving the stacked
    # (K, P, bn) power rows by swapaxes + reshape instead gave series 4-7,
    # 12-15 and 20-23 of a tile wrong Gram blocks on TPU v5e at
    # block_n <= 256 (correct from 1024 up, and in interpret mode).
    wmat_rows, lhs_rows = [], []
    for s in range(p):
        xs, ys, ws = x[s:s + 1], y[s:s + 1], w[s:s + 1]
        pows = [jnp.ones_like(xs)]
        for _ in range(degree):
            pows.append(pows[-1] * xs)
        for row in pows + [ys]:
            wmat_rows.append(row)
            lhs_rows.append(row * ws)
    pad = K_PAD - p * k
    if pad:
        zpad = jnp.zeros((pad, bn), accum_dtype)
        wmat_rows.append(zpad)
        lhs_rows.append(zpad)
    wmat = jnp.concatenate(wmat_rows, axis=0)
    lhs = jnp.concatenate(lhs_rows, axis=0)

    return jax.lax.dot_general(
        lhs, wmat, (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=accum_dtype)[None]


def _packed_moments_kernel(x_ref, y_ref, w_ref, g_ref, *maybe_c, degree: int,
                           accum_dtype, precision):
    """One (group, block) grid step with P series packed into the sublanes."""
    c_ref = maybe_c[0] if maybe_c else None
    i = pl.program_id(1)
    _accum_init(i, (g_ref,) + ((c_ref,) if c_ref is not None else ()))

    update = _packed_tile_update(x_ref[0], y_ref[0], w_ref[0], degree,
                                 accum_dtype, precision)
    _accum_add(update, g_ref, c_ref)


def _packed_moments_db_kernel(x_hbm, y_hbm, w_hbm, g_ref, *maybe_c,
                              degree: int, accum_dtype, precision,
                              block_n: int, n_blocks: int, nbuf: int, p: int):
    """One grid step per GROUP; the n-block loop runs in-kernel over an
    ``nbuf``-slot VMEM ring with explicit async copies: block k+1's three
    DMAs are in flight while block k's matmul runs on the MXU."""
    c_ref = maybe_c[0] if maybe_c else None
    gi = pl.program_id(0)
    in_dtype = x_hbm.dtype

    def body(xs, ys, ws, sem):
        g_ref[...] = jnp.zeros_like(g_ref)
        if c_ref is not None:
            c_ref[...] = jnp.zeros_like(c_ref)

        def dmas(slot, i):
            sl = pl.ds(i * block_n, block_n)
            return (pltpu.make_async_copy(x_hbm.at[gi, :, sl], xs.at[slot],
                                          sem.at[slot, 0]),
                    pltpu.make_async_copy(y_hbm.at[gi, :, sl], ys.at[slot],
                                          sem.at[slot, 1]),
                    pltpu.make_async_copy(w_hbm.at[gi, :, sl], ws.at[slot],
                                          sem.at[slot, 2]))

        for d in dmas(0, 0):                       # warm the pipeline
            d.start()

        def step(i, _):
            slot = jax.lax.rem(i, nbuf)
            nxt = jax.lax.rem(i + 1, nbuf)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():                       # block k+1 in flight...
                for d in dmas(nxt, i + 1):
                    d.start()

            for d in dmas(slot, i):                # ...while block k lands
                d.wait()
            update = _packed_tile_update(xs[slot], ys[slot], ws[slot],
                                         degree, accum_dtype, precision)
            _accum_add(update, g_ref, c_ref)
            return 0

        jax.lax.fori_loop(0, n_blocks, step, 0)

    pl.run_scoped(
        body,
        xs=pltpu.VMEM((nbuf, p, block_n), in_dtype),
        ys=pltpu.VMEM((nbuf, p, block_n), in_dtype),
        ws=pltpu.VMEM((nbuf, p, block_n), in_dtype),
        sem=pltpu.SemaphoreType.DMA((nbuf, 3)),
    )


def _fused_report_kernel(x_ref, y_ref, w_ref, coef_ref, o_ref, *, degree: int,
                         accum_dtype):
    """Evaluate + residual + SSE/R sums in one pass; no HBM intermediates."""
    i = pl.program_id(1)
    _accum_init(i, (o_ref,))

    x = x_ref[...].astype(accum_dtype)       # (rb, block_n)
    y = y_ref[...].astype(accum_dtype)
    w = w_ref[...].astype(accum_dtype)
    c = coef_ref[...].astype(accum_dtype)    # (rb, 128): coeffs then zero pad

    # Horner evaluation — same O(m) ladder as basis.evaluate, in-register.
    f = jnp.broadcast_to(c[:, degree:degree + 1], x.shape)
    for k in range(degree - 1, -1, -1):
        f = f * x + c[:, k:k + 1]
    e = y - f

    sums = (w, w * y, w * y * y, w * f, w * f * f, w * y * f, w * e * e)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K_PAD), 1)
    update = jnp.zeros(o_ref.shape, accum_dtype)
    for j, s in enumerate(sums):
        update = update + jnp.where(lane == j, jnp.sum(s, axis=1,
                                                       keepdims=True),
                                    jnp.zeros((), accum_dtype))
    o_ref[...] += update


def _moments_call(kernel_fn, grid, in_specs, out_spec, b_out, *,
                  compensated, accum_dtype, interpret, args, name=None):
    """Shared pallas_call plumbing for the plain/packed moment kernels."""
    struct = jax.ShapeDtypeStruct((b_out, K_PAD, K_PAD), accum_dtype)
    if compensated:
        out = pl.pallas_call(
            kernel_fn, grid=grid, in_specs=in_specs,
            out_specs=[out_spec, out_spec], out_shape=[struct, struct],
            interpret=interpret, name=name)(*args)
        return out[0]   # Kahan: the corrected sum is the primary tile
    return pl.pallas_call(
        kernel_fn, grid=grid, in_specs=in_specs,
        out_specs=out_spec, out_shape=struct, interpret=interpret,
        name=name)(*args)


@functools.partial(jax.jit,
                   static_argnames=("degree", "block_n", "interpret",
                                    "accum_dtype", "compensated"))
def moments_extended(x: jax.Array, y: jax.Array, weights: jax.Array, *,
                     degree: int, block_n: int = DEFAULT_BLOCK_N,
                     accum_dtype=jnp.float32,
                     compensated: bool = False,
                     interpret: bool = False) -> jax.Array:
    """Raw kernel output: (B, K_PAD, K_PAD) extended Gram per batch row.

    x, y, weights: (B, n) with n % block_n == 0 and B <= ROW_BLOCK or a
    multiple of it (ops.py handles padding — padded tail points and rows
    carry weight 0 so they contribute nothing).
    """
    if x.ndim != 2:
        raise ValueError("moments_extended expects (B, n) inputs")
    b, n = x.shape
    if n % block_n:
        raise ValueError(f"n={n} must be a multiple of block_n={block_n}")
    if degree + 2 > K_PAD:
        raise ValueError(f"degree {degree} too large for K_PAD={K_PAD}")
    rb = _checked_row_block(b)

    kernel_fn = functools.partial(_moments_kernel, degree=degree,
                                  accum_dtype=accum_dtype,
                                  precision=gram_precision(n))
    in_spec = pl.BlockSpec((rb, block_n), lambda bi, ni: (bi, ni))
    out_spec = pl.BlockSpec((rb, K_PAD, K_PAD), lambda bi, ni: (bi, 0, 0))
    return _moments_call(kernel_fn, (b // rb, n // block_n), [in_spec] * 3,
                         out_spec, b, compensated=compensated,
                         accum_dtype=accum_dtype, interpret=interpret,
                         args=(x, y, weights))


def flat_block(n: int) -> int:
    """Points a grid step of the lone-series pass: FLAT_BLOCK_N, or n
    rounded up to whole slabs when that is less."""
    return min(FLAT_BLOCK_N, -(-n // SLAB) * SLAB)


@functools.partial(jax.jit,
                   static_argnames=("degree", "block_n", "interpret",
                                    "accum_dtype", "compensated"))
def moments_flat(x: jax.Array, y: jax.Array, weights: jax.Array | None = None,
                 *, degree: int, block_n: int | None = None,
                 accum_dtype=jnp.float32,
                 compensated: bool = False,
                 interpret: bool = False) -> jax.Array:
    """The (degree+2, degree+2) extended Gram of one series:
    [[A, Vᵀy], [yᵀV, Σwy²]] with the Hankel A[j, k] = S_{j+k}.

    x, y and weights (None: unweighted, and no weights stream is read) are
    the caller's (n,) arrays at any n, read in place: the grid covers
    cdiv(n, block_n) blocks (``flat_block(n)`` by default; a caller's
    block_n must be a multiple of SLAB) and the kernel masks the points
    past n, so nothing is padded, copied or relaid out.  The sums are VPU
    products in accum_dtype; no matmul runs.  The pallas_call is named
    ``moments_plain``, which is how a device trace shows it.
    """
    if x.ndim != 1 or y.shape != x.shape or (
            weights is not None and weights.shape != x.shape):
        raise ValueError("moments_flat expects equal (n,) inputs")
    if degree + 2 > K_PAD:
        raise ValueError(f"degree {degree} too large for K_PAD={K_PAD}")
    n = x.shape[0]
    if n >= 1 << 31:
        raise ValueError(f"n={n}: the kernel counts points in int32")
    if block_n is None:
        block_n = flat_block(n)
    if block_n % SLAB:
        raise ValueError(f"block_n={block_n} must be a multiple of {SLAB}")
    weighted = weights is not None
    kernel_fn = functools.partial(_moments_flat_kernel, n=n, degree=degree,
                                  weighted=weighted, accum_dtype=accum_dtype)
    args = (x, y) + ((weights,) if weighted else ())
    partials = (flat_sums(degree),) + SLAB_SHAPE
    lane_sums = pl.pallas_call(
        kernel_fn, grid=(pl.cdiv(n, block_n),),
        in_specs=[pl.BlockSpec((block_n,), lambda ni: (ni,))] * len(args),
        out_specs=pl.BlockSpec(partials, lambda ni: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(partials, accum_dtype),
        scratch_shapes=([pltpu.VMEM(partials, accum_dtype)] if compensated
                        else []),
        interpret=interpret, name="moments_plain")(*args)
    s = lane_sums.sum(axis=(1, 2))
    if not weighted:
        s = s.at[0].set(n)
    j = np.arange(degree + 1)
    hankel = s[j[:, None] + j[None, :]]
    vty = s[2 * degree + 1 + j]
    return jnp.block([[hankel, vty[:, None]], [vty[None, :], s[-1:, None]]])


@functools.partial(jax.jit,
                   static_argnames=("degree", "block_n", "interpret",
                                    "accum_dtype", "compensated", "nbuf"))
def moments_packed_extended(x: jax.Array, y: jax.Array, weights: jax.Array, *,
                            degree: int, block_n: int = DEFAULT_BLOCK_N,
                            accum_dtype=jnp.float32,
                            compensated: bool = False,
                            nbuf: int = 0,
                            interpret: bool = False) -> jax.Array:
    """Packed kernel output: (G, K_PAD, K_PAD); series p of group g lives in
    the diagonal block ``out[g, p*K:(p+1)*K, p*K:(p+1)*K]`` (K = degree+2).

    x, y, weights: (G, P, n) with P == packing_factor(degree) and
    n % block_n == 0. Use ``extract_packed`` to pull per-series blocks.

    ``nbuf >= 2`` selects the explicit multi-buffered DMA pipeline (see
    module docstring §Double buffering): same per-block math and
    accumulation order, prefetch of block k+1 overlapped with block k's
    matmul. ``nbuf=0`` (default) is the grid-streamed form.
    """
    if x.ndim != 3:
        raise ValueError("moments_packed_extended expects (G, P, n) inputs")
    g, p, n = x.shape
    if p != packing_factor(degree):
        raise ValueError(f"P={p} != packing_factor({degree})="
                         f"{packing_factor(degree)}")
    if n % block_n:
        raise ValueError(f"n={n} must be a multiple of block_n={block_n}")
    if nbuf == 1 or nbuf < 0:
        raise ValueError(f"nbuf={nbuf}: 0 (grid-streamed) or >= 2 "
                         "(multi-buffered ring)")

    if nbuf >= 2:
        n_blocks = n // block_n
        kernel_fn = functools.partial(
            _packed_moments_db_kernel, degree=degree,
            accum_dtype=accum_dtype, precision=gram_precision(n),
            block_n=block_n,
            n_blocks=n_blocks, nbuf=min(nbuf, n_blocks) if n_blocks > 1
            else 2, p=p)
        in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 3
        out_spec = pl.BlockSpec((1, K_PAD, K_PAD), lambda gi: (gi, 0, 0))
        return _moments_call(kernel_fn, (g,), in_specs, out_spec, g,
                             compensated=compensated,
                             accum_dtype=accum_dtype, interpret=interpret,
                             args=(x, y, weights))

    kernel_fn = functools.partial(_packed_moments_kernel, degree=degree,
                                  accum_dtype=accum_dtype,
                                  precision=gram_precision(n))
    in_spec = pl.BlockSpec((1, p, block_n), lambda gi, ni: (gi, 0, ni))
    out_spec = pl.BlockSpec((1, K_PAD, K_PAD), lambda gi, ni: (gi, 0, 0))
    return _moments_call(kernel_fn, (g, n // block_n), [in_spec] * 3,
                         out_spec, g, compensated=compensated,
                         accum_dtype=accum_dtype, interpret=interpret,
                         args=(x, y, weights))


def extract_packed(g: jax.Array, degree: int) -> jax.Array:
    """(G, K_PAD, K_PAD) packed Gram -> (G*P, K, K) per-series blocks."""
    k = degree + 2
    p = packing_factor(degree)
    blocks = jnp.stack([g[:, i * k:(i + 1) * k, i * k:(i + 1) * k]
                        for i in range(p)], axis=1)       # (G, P, K, K)
    return blocks.reshape(g.shape[0] * p, k, k)


@functools.partial(jax.jit,
                   static_argnames=("degree", "block_n", "interpret",
                                    "accum_dtype"))
def fused_report_sums(x: jax.Array, y: jax.Array, weights: jax.Array,
                      coeffs: jax.Array, *, degree: int,
                      block_n: int = DEFAULT_BLOCK_N,
                      accum_dtype=jnp.float32,
                      interpret: bool = False) -> jax.Array:
    """One streamed pass over (B, n) data: per-series report sums.

    Returns (B, K_PAD) where lanes SUM_W..SUM_SSE hold
    [Σw, Σwy, Σwy², Σwf, Σwf², Σwyf, Σw(y-f)²] and the rest are zero.
    ``coeffs``: (B, K_PAD) monomial coefficients, zero-padded past degree.
    B <= ROW_BLOCK or a multiple of it (ops.py pads with zero-weight rows).
    Everything ``fit_report`` derives (SSE, R) follows from these sums with
    O(B) work — no (B, n) fitted/residual arrays ever touch HBM.
    """
    if x.ndim != 2 or coeffs.shape != (x.shape[0], K_PAD):
        raise ValueError("fused_report_sums expects x:(B,n), coeffs:(B,128)")
    b, n = x.shape
    if n % block_n:
        raise ValueError(f"n={n} must be a multiple of block_n={block_n}")
    rb = _checked_row_block(b)

    kernel_fn = functools.partial(_fused_report_kernel, degree=degree,
                                  accum_dtype=accum_dtype)
    data_spec = pl.BlockSpec((rb, block_n), lambda bi, ni: (bi, ni))
    row_spec = pl.BlockSpec((rb, K_PAD), lambda bi, ni: (bi, 0))
    return pl.pallas_call(
        kernel_fn,
        grid=(b // rb, n // block_n),
        in_specs=[data_spec, data_spec, data_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, K_PAD), accum_dtype),
        interpret=interpret,
    )(x, y, weights, coeffs)
