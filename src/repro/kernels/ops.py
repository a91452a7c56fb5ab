"""Jitted public wrappers around the Pallas moment/report kernels.

Handles: batch/flat shapes, tail padding (weight-masked so padding is inert),
block size choice, CPU fallback (interpret mode), packed-vs-plain path
selection, and extraction of the ``Moments`` sufficient statistics from the
kernels' extended Gram output.  A lone (n,) series on the plain layout is
not padded: ``kernel.moments_flat`` reads the caller's arrays in place, so
a series may fill the device's memory (no ones array, no padded copies),
and sums its powers on the VPU with no matmul.

Path selection (``moments(..., packing="auto")``):
  * **packed** — batch of ≥ 2 series and packing_factor(degree) ≥ 2: pack
    P = 128 // (degree+2) series per MXU tile (≈ P× fewer FLOPs per fit; see
    the layout diagram in ``repro.kernels.moments``). Batches not divisible
    by P are padded with zero-weight tail series whose exact-zero Gram
    blocks are sliced away.
  * **plain** — single series, or degree > 62 (P < 2): one series per tile;
    a lone (n,) series takes ``kernel.moments_flat``'s power sums instead.
  * the pure-jnp path stays in ``repro.core.gram_moments`` (the
    ``repro.engine`` plan layer picks between them; ``engine="reference"``
    forces it).

Count semantics: ``Moments.count`` from this module is the TRUE number of
contributing data points — points with nonzero weight, excluding padding —
and ``Moments.weight_sum`` is Σw (== the kernel's raw G[0,0] entry).  The
jnp path records the same split, so kernel- and jnp-produced states mix
freely.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.moments import Moments
from repro.kernels import moments as kernel


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _auto_block(n: int) -> int:
    # smallest lane-aligned block that covers short series in one step;
    # large series stream in DEFAULT_BLOCK_N tiles.
    return min(kernel.DEFAULT_BLOCK_N, max(128, -(-n // 128) * 128))


def _pad_tail(arrs, pad):
    if not pad:
        return arrs
    zpad = [(0, 0)] * (arrs[0].ndim - 1) + [(0, pad)]
    return [jnp.pad(a, zpad) for a in arrs]


def _pad_rows(arrs, multiple):
    """Pad the leading (series) axis to a multiple with zero rows; callers
    pad the weights with zeros too, so padded series contribute nothing."""
    pad = (-arrs[0].shape[0]) % multiple
    if not pad:
        return arrs
    zpad = [(0, pad)] + [(0, 0)] * (arrs[0].ndim - 1)
    return [jnp.pad(a, zpad) for a in arrs]


def _true_count(weights, batch, n, dtype):
    """Number of contributing points per series (not Σw — see module doc)."""
    if weights is None:
        return jnp.full(batch, n, dtype)
    return jnp.sum((weights != 0).astype(dtype), axis=-1)


def _weight_sum(weights, batch, n, dtype):
    if weights is None:
        return jnp.full(batch, n, dtype)
    return jnp.sum(weights, axis=-1).astype(dtype)


def _from_gram(g, degree, count, weight_sum):
    """``Moments`` from the (..., K, K) extended Gram, K >= degree+2."""
    m1 = degree + 1
    return Moments(gram=g[..., :m1, :m1], vty=g[..., :m1, m1],
                   yty=g[..., m1, m1], count=count, weight_sum=weight_sum)


@functools.partial(jax.jit, static_argnames=("degree", "block_n", "interpret",
                                             "accum_dtype", "packing",
                                             "compensated", "nbuf"))
def moments(x: jax.Array, y: jax.Array, degree: int, *,
            weights: jax.Array | None = None,
            block_n: int | None = None,
            accum_dtype=jnp.float32,
            packing: str = "auto",
            compensated: bool = False,
            nbuf: int = 0,
            interpret: bool | None = None) -> Moments:
    """Drop-in kernel-backed equivalent of ``repro.core.gram_moments``.

    Accepts (n,) or (B, n) inputs of any float dtype; returns f32-accumulated
    Moments with matching batch shape. ``packing`` ∈ {"auto", "packed",
    "plain"} picks the tile layout; ``compensated=True`` enables the Kahan
    two-float Gram accumulator (large-n precision, Skala arXiv:1802.07591);
    ``nbuf >= 2`` selects the packed kernel's explicit multi-buffered DMA
    pipeline (prefetch block k+1 while block k's matmul runs — pick the
    tile width with ``repro.kernels.tune.autotune_block_n``).
    """
    if packing not in ("auto", "packed", "plain"):
        raise ValueError(f"packing={packing!r}; expected 'auto', 'packed' "
                         "or 'plain'")
    if interpret is None:
        interpret = _should_interpret()
    if accum_dtype is None:
        accum_dtype = jnp.float32
    flat = x.ndim == 1
    b, n = (1,) + x.shape if flat else x.shape
    pfac = kernel.packing_factor(degree)
    use_packed = (packing == "packed"
                  or (packing == "auto" and b > 1 and pfac > 1))
    if use_packed and pfac < 2:
        raise ValueError(f"degree {degree} leaves no room to pack "
                         f"(packing_factor={pfac}); use packing='plain'")
    if nbuf >= 2 and not use_packed:
        raise ValueError("nbuf (multi-buffered DMA pipeline) is a packed-"
                         "kernel knob; this call resolved to the plain "
                         "layout")
    if flat and not use_packed:
        # a lone series on the plain layout: the kernel reads the caller's
        # arrays in place, at any n, in blocks derived from n
        g = kernel.moments_flat(x, y, weights, degree=degree,
                                block_n=block_n, accum_dtype=accum_dtype,
                                compensated=compensated, interpret=interpret)
        return _from_gram(g, degree, _true_count(weights, (), n, accum_dtype),
                          _weight_sum(weights, (), n, accum_dtype))
    if block_n is None:
        block_n = _auto_block(n)
    if flat:
        x, y = x[None], y[None]
        if weights is not None:
            weights = weights[None]
    count = _true_count(weights, (b,), n, accum_dtype)
    weight_sum = _weight_sum(weights, (b,), n, accum_dtype)

    w = jnp.ones_like(x) if weights is None else weights
    x, y, w = _pad_tail([x, y, w], (-n) % block_n)
    # zero weight ⇒ padded tail contributes nothing

    if use_packed:
        # zero-weight tail series: exact-zero blocks
        x, y, w = _pad_rows([x, y, w], pfac)
        shape = (x.shape[0] // pfac, pfac, x.shape[-1])
        gp = kernel.moments_packed_extended(
            x.reshape(shape), y.reshape(shape), w.reshape(shape),
            degree=degree, block_n=block_n, accum_dtype=accum_dtype,
            compensated=compensated, nbuf=nbuf, interpret=interpret)
        g = kernel.extract_packed(gp, degree)[:b]         # (b, m+2, m+2)
    else:
        x, y, w = _pad_rows([x, y, w], kernel.row_block(b))
        g = kernel.moments_extended(x, y, w, degree=degree, block_n=block_n,
                                    accum_dtype=accum_dtype,
                                    compensated=compensated,
                                    interpret=interpret)[:b]
    out = _from_gram(g, degree, count, weight_sum)
    if flat:
        out = jax.tree.map(lambda a: a[0], out)
    return out


@functools.partial(jax.jit, static_argnames=("block_n", "interpret",
                                             "accum_dtype"))
def fused_report_sums(x: jax.Array, y: jax.Array, coeffs: jax.Array, *,
                      weights: jax.Array | None = None,
                      block_n: int | None = None,
                      accum_dtype=jnp.float32,
                      interpret: bool | None = None) -> dict[str, jax.Array]:
    """One-pass evaluation/residual sums for ``core.fit.fit_report_streamed``.

    x, y: (..., n); coeffs: (..., m+1) monomial coefficients in the same
    (already domain-mapped) x. Returns a dict of (...,)-shaped sums:
    ``sw, sy, syy, sf, sff, syf, sse`` — Σw, Σwy, Σwy², Σwf, Σwf², Σwyf,
    Σw(y-f)². Padding rides in with weight 0 and contributes nothing.
    """
    if interpret is None:
        interpret = _should_interpret()
    if accum_dtype is None:
        accum_dtype = jnp.float32
    degree = coeffs.shape[-1] - 1
    if degree + 1 > kernel.K_PAD:
        raise ValueError(f"degree {degree} too large for K_PAD={kernel.K_PAD}")
    batch = x.shape[:-1]
    n = x.shape[-1]
    xb = x.reshape(-1, n)
    yb = y.reshape(-1, n)
    b = xb.shape[0]
    wb = (jnp.ones_like(xb) if weights is None
          else jnp.broadcast_to(weights, x.shape).reshape(-1, n))
    cb = jnp.broadcast_to(coeffs, batch + coeffs.shape[-1:]).reshape(b, -1)
    cb = jnp.pad(cb, [(0, 0), (0, kernel.K_PAD - cb.shape[-1])])

    if block_n is None:
        block_n = _auto_block(n)
    xb, yb, wb = _pad_tail([xb, yb, wb], (-n) % block_n)
    xb, yb, wb, cb = _pad_rows([xb, yb, wb, cb], kernel.row_block(b))

    sums = kernel.fused_report_sums(
        xb, yb, wb, cb.astype(accum_dtype), degree=degree, block_n=block_n,
        accum_dtype=accum_dtype, interpret=interpret)[:b]
    names = ("sw", "sy", "syy", "sf", "sff", "syf", "sse")
    return {name: sums[:, j].reshape(batch)
            for j, name in enumerate(names)}
