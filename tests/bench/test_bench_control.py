"""The control, the reference fit in bfloat16 in the program's place,
comes out not correct in each cell kind, at a test size on the CPU (on
the chip it runs at the cells' own sizes: bench/control.py)."""
import jax
import pytest

from _cells import SECONDS, small_cell  # noqa: E402  (sets sys.path)

from bench import control  # noqa: E402


@pytest.mark.parametrize("workload", ["large_fit", "serve_overload"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_the_control_fails(workload, seed):
    cell = small_cell(workload)
    if "points" in cell.config:
        # 64 of the control's bfloat16 blocks, as the chip's cells have
        # tens of thousands
        cell.config = dict(cell.config, points=1 << 18)
    checks = control.control_readings(cell, seed, SECONDS,
                                      jax.devices()[:1])
    failed = [c.name for c in checks if not c.ok]
    assert failed


def test_the_control_computes_in_bfloat16():
    import jax.numpy as jnp
    x = jnp.linspace(-1.0, 1.0, 301)
    g = control.bf16_gram(x, 2 * x, 301, 3)
    assert g.dtype == jnp.bfloat16 and g.shape == (5, 5)
    assert float(g[0, 0]) != 301.0   # 301 is no bfloat16 number


def test_the_float32_sums_control_rounds_only_its_operands():
    import jax.numpy as jnp
    import numpy as np
    x = jnp.linspace(-1.0, 1.0, 301)
    g = control.bf16_gram(x, 2 * x, 301, 3, jnp.float32)
    assert g.dtype == jnp.float32 and g.shape == (5, 5)
    assert float(g[0, 0]) == 301.0   # the count is exact in float32
    xb = np.asarray(x.astype(jnp.bfloat16), np.float64)
    a = np.stack([np.ones_like(xb), xb,
                  np.asarray((x * x).astype(jnp.bfloat16), np.float64)])
    assert np.allclose(np.asarray(g)[:3, :3], a @ a.T, rtol=1e-6, atol=1e-6)
