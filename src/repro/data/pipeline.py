"""Synthetic data for the paper's own workload: seeded polynomial series."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def curve_dataset(n: int, degree: int = 3, noise: float = 1.0,
                  seed: int = 0, batch: tuple[int, ...] = ()):
    """Synthetic polynomial datasets for the paper's own workload: returns
    (x, y, true_coeffs). x ~ U[-10, 10]; y = poly(x) + N(0, noise)."""
    rng = np.random.default_rng(seed)
    coeffs = rng.normal(0, 1, batch + (degree + 1,))
    x = rng.uniform(-10, 10, batch + (n,))
    powers = np.stack([x ** k for k in range(degree + 1)], axis=-1)
    y = np.einsum("...nk,...k->...n", powers, coeffs)
    y = y + rng.normal(0, noise, y.shape)
    return (jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
            jnp.asarray(coeffs, jnp.float32))
