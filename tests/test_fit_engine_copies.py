"""The fit server's host-device copies: one buffer in and one packed array
out per bucket dispatch, each split exactly as the seven arrays and six
answers they carry.  Every test runs with 64-bit mode off and on."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import robust as robust_lib
from repro.serve import FitServeConfig, FitServeEngine
from repro.serve import fit_engine as fe

X64 = pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
HUBER = api.FitSpec(degree=3, method="irls")
TUKEY = api.FitSpec(degree=3, method="irls",
                    irls=api.IRLSOptions(loss="tukey", c=3.0))


def _series(rng, n):
    x = np.sort(rng.uniform(-2, 2, n)).astype(np.float32)
    y = (1.0 - x + 0.5 * x ** 3 + rng.normal(0, 0.1, n)).astype(np.float32)
    y[::7] += 5.0                     # outliers, for the IRLS slots
    return x, y


def _parent_layout(b):
    """The seven arrays a dispatch sent before they shared one buffer,
    built as the engine built them, from a copy of the bucket's slots and
    queue: x, y, w (n_slots, width), keep, rmask, loss_id (int32), cval."""
    slot_req, queue = list(b.slot_req), list(b.queue)
    pos, reset = b.slot_pos.copy(), b.reset.copy()
    for s, req in enumerate(slot_req):
        if req is None and queue:
            slot_req[s] = queue.pop(0)
            pos[s] = 0
            reset[s] = True
    n, w = len(slot_req), b.width
    xh = np.zeros((n, w), np.float32)
    yh = np.zeros((n, w), np.float32)
    wh = np.zeros((n, w), np.float32)
    rmask = np.zeros(n, np.float32)
    loss_id = np.zeros(n, np.int32)
    cval = np.ones(n, np.float32)
    for s, req in enumerate(slot_req):
        if req is None:
            continue
        lo = int(pos[s])
        m = req.x[lo:lo + w].shape[0]
        xh[s, :m] = req.x[lo:lo + w]
        yh[s, :m] = req.y[lo:lo + w]
        wh[s, :m] = 1.0
        if req.spec.method == "irls":
            rmask[s] = 1.0
            loss_id[s] = robust_lib.LOSS_IDS[req.spec.irls.loss]
            cval[s] = robust_lib.resolve_tuning(req.spec.irls.loss,
                                                req.spec.irls.c)
    keep = np.where(reset, 0.0, 1.0).astype(np.float32)
    return xh, yh, wh, keep, rmask, loss_id, cval


@X64
def test_the_step_buffer_splits_into_the_seven_arrays(x64):
    with jax.enable_x64(x64):
        eng = FitServeEngine(FitServeConfig(degree=3, n_slots=2,
                                            buckets=(16, 64)))
        rng = np.random.default_rng(0)
        # more requests than slots (slot reuse), one over three chunks
        # of the wide bucket (keep = 1 mid-series), both IRLS losses
        for n, spec in ((12, None), (9, HUBER), (40, TUKEY), (150, None),
                        (5, TUKEY), (30, HUBER), (64, None)):
            eng.submit(*_series(rng, n), spec=spec)
        seen = []
        pack = eng._pack

        def recording_pack(b):
            want = _parent_layout(b)
            out = pack(b)
            if out is not None:
                seen.append((b.width, want, out[0]))
            return out

        eng._pack = recording_pack
        eng.run()

        def split(buf, width):
            # as the compiled step splits it: loss ids back to int32
            *cols, cval = fe.split_step_buffer(buf, width)
            return (*cols[:5], cols[5].astype(np.int32), cval)

        split_jit = jax.jit(split, static_argnums=1)
        assert len(seen) == eng.h2d_copies > 4
        kinds = set()
        for width, want, buf in seen:
            assert buf.dtype == np.float32
            assert buf.shape == (2, 3 * width + 4)
            for got in (split(buf, width),
                        split_jit(jnp.asarray(buf), width)):
                for g, w in zip(got, want):
                    g = np.asarray(g)
                    assert g.dtype == w.dtype
                    np.testing.assert_array_equal(g, w)
            kinds.update(zip(want[3], want[5], want[6]))
        # (keep, loss_id, cval): a fresh Tukey slot, a fresh Huber slot,
        # a plain slot mid-series
        huber = np.float32(robust_lib.resolve_tuning("huber", None))
        assert {(0.0, 1, 3.0), (0.0, 0, huber), (1.0, 0, 1.0)} <= kinds


@X64
def test_the_host_split_of_the_packed_answers_is_bitwise(x64):
    with jax.enable_x64(x64):
        eng = FitServeEngine(FitServeConfig(degree=3, n_slots=4,
                                            buckets=(64,)))
        rng = np.random.default_rng(1)
        for n in (50, 20):             # slots 2 and 3 stay all-zero
            eng.submit(*_series(rng, n))
        eng.run()
        state = eng.buckets[0].state
        solve = jax.jit(fe._spec_solve_from_state, static_argnums=(1, 2))
        for spec in (eng.fixed_spec,
                     dataclasses.replace(eng.fixed_spec, ridge=0.0)):
            want = [np.asarray(a) for a in solve(state, spec, 3)]
            packed = jax.jit(lambda st, spec=spec: fe.pack_solved(
                fe._spec_solve_from_state(st, spec, 3)))(state)
            assert packed.shape == (4, 4 + 5)
            got = fe.unpack_solved(np.asarray(packed))
            for g, w in zip(got, want):
                assert g.dtype == w.dtype and g.shape == w.shape
                np.testing.assert_array_equal(g, w)
        # without the ridge the empty slots' Gram is singular: an
        # infinite condition and the fallback, beside finite fitted slots
        cond, fb = got[4], got[5]
        assert np.isinf(cond[2:]).all() and fb[2:].all()
        assert np.isfinite(cond[:2]).all() and not fb[:2].any()


@X64
def test_a_mixed_step_packs_only_the_default_group(x64):
    with jax.enable_x64(x64):
        eng = FitServeEngine(FitServeConfig(degree=3, n_slots=6,
                                            buckets=(128,)))
        rng = np.random.default_rng(2)
        lower = api.FitSpec(degree=2)
        gauss = api.FitSpec(degree=3, numerics=api.NumericsPolicy(
            solver="gauss", fallback="svd"))
        kinds = [{}, {"spec": HUBER}, {"spec": TUKEY}, {"spec": lower},
                 {"spec": gauss}, {"degree": "auto"}]
        reqs = [eng.submit(*_series(rng, n), **k)
                for n, k in zip((100, 128, 60, 90, 33, 120), kinds)]
        eng.run()
        assert eng._step_no == 1 and all(r.done for r in reqs)
        state = eng.buckets[0].state
        solve, sweep = fe.make_spec_solve(3), fe.make_spec_sweep(3)
        for s, req in enumerate(reqs):
            ref = fe.FitRequest(req.uid, req.x, req.y, spec=req.spec,
                                auto=req.auto)
            if req.auto:
                outs = fe.auto_outputs(*sweep(state, req.spec))
                fe.fill_auto_result(ref, req.spec, outs, "aicc", s)
                for k, v in ref.scores.items():
                    np.testing.assert_array_equal(req.scores[k], v)
                np.testing.assert_array_equal(req.condition_ladder,
                                              ref.condition_ladder)
            else:
                solved = tuple(np.asarray(a)
                               for a in solve(state, req.spec))
                fe.fill_fixed_result(ref, req.spec, solved, s)
            np.testing.assert_array_equal(req.coeffs, ref.coeffs)
            assert req.coeffs.dtype == ref.coeffs.dtype
            assert ((req.degree, req.sse, req.r, req.count, req.condition,
                     req.fallback_used)
                    == (ref.degree, ref.sse, ref.r, ref.count,
                        ref.condition, ref.fallback_used)), s
        # one copy in; one packed copy back for the default request, six
        # for each of the four other fixed specs, and one per output of
        # the auto request's sweep
        n_auto = len(fe.auto_outputs(*sweep(state, reqs[-1].spec))["scores"])
        assert eng.h2d_copies == 1
        assert eng.d2h_copies == 1 + 4 * 6 + n_auto + 5
