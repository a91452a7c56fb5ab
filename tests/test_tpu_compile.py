"""Ahead-of-time compiles of the main-path kernels for a described v5e chip.

Nothing runs: each test lowers a ``kernels.ops`` entry point with
``interpret=False`` for one device of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler, which refuses what the chip would refuse
(block shapes that break the (8, 128) tiling, too much VMEM).  Interpret-mode
parity tests cannot see those refusals.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every pytest worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable cannot be read back from the persistent
    # cache without the chip; keep it out of any cache this process uses
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _compile_text(fn, shapes, sharding):
    return _compile(fn, shapes, sharding).as_text()


@pytest.mark.parametrize("compensated", [False, True])
def test_plain_single_series_large(one_chip, compensated):
    n = 1 << 27
    text = _compile_text(
        functools.partial(ops.moments, degree=3, packing="plain",
                          compensated=compensated, interpret=False),
        [(n,), (n,)], one_chip)
    assert "tpu_custom_call" in text


# a series of 1e9 f32 points: 8 GB of x and y, half of the chip's 16 GB
RESIDENT_POINTS = 1_000_000_000
IN_PLACE_TEMP_BYTES = 64 << 20


def test_plain_single_series_resident_in_place(one_chip):
    """The lone-series plain moment pass reads a resident series in place:
    no ones array, no padded copies, at a length no block divides."""
    compiled = _compile(
        functools.partial(ops.moments, degree=3, packing="plain",
                          interpret=False),
        [(RESIDENT_POINTS,), (RESIDENT_POINTS,)], one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < IN_PLACE_TEMP_BYTES
    assert "moments_plain" in compiled.as_text()


@pytest.mark.parametrize("degree, compensated, weighted",
                         [(3, True, False), (9, False, False),
                          (3, False, True)],
                         ids=["compensated", "degree9", "weighted"])
def test_plain_single_series_resident_variants(one_chip, degree, compensated,
                                               weighted):
    """The lone-series pass's other variants hold its VMEM budget and its
    in-place read at the resident size too."""
    def fit_moments(x, y, *w):
        return ops.moments(x, y, degree, weights=w[0] if w else None,
                           packing="plain", compensated=compensated,
                           interpret=False)

    compiled = _compile(fit_moments,
                        [(RESIDENT_POINTS,)] * (3 if weighted else 2),
                        one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < IN_PLACE_TEMP_BYTES
    assert "moments_plain" in compiled.as_text()


def test_resident_fit_program_in_place(one_chip, monkeypatch):
    """The whole fixed-degree fit (domain, moments, count, solve, report)
    of a resident 1e9-point series holds no copy of it either."""
    from repro import api
    from repro.api import executors
    monkeypatch.setattr(ops, "_should_interpret", lambda: False)
    arg = jax.ShapeDtypeStruct((RESIDENT_POINTS,), jnp.float32,
                               sharding=one_chip)
    spec = api.FitSpec(degree=3, engine="kernel_plain")
    compiled = executors._fit_lse_fixed.lower(arg, arg, None, spec).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < IN_PLACE_TEMP_BYTES
    assert "moments_plain" in compiled.as_text()


@pytest.mark.parametrize("degree", [3, 5])
def test_packed_serve_bucket(one_chip, degree):
    text = _compile_text(
        functools.partial(ops.moments, degree=degree, packing="packed",
                          interpret=False),
        [(8, 2048), (8, 2048)], one_chip)
    assert "tpu_custom_call" in text


def test_plain_batched(one_chip):
    text = _compile_text(
        functools.partial(ops.moments, degree=3, packing="plain",
                          interpret=False),
        [(8, 1 << 16), (8, 1 << 16)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 8, 12])
def test_fused_report(one_chip, batch):
    text = _compile_text(
        functools.partial(ops.fused_report_sums, interpret=False),
        [(batch, 1 << 20), (batch, 1 << 20), (batch, 4)], one_chip)
    assert "tpu_custom_call" in text
