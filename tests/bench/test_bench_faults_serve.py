"""A run of the serving cell whose timed path is broken comes out not
correct: once for each fault the fit server can have."""
import numpy as np
import pytest

from _cells import run_small  # noqa: E402  (sets sys.path)
from faults import FAULTS  # noqa: E402


def test_a_sound_run_is_correct():
    got = run_small("serve_overload")
    assert got["correct"], got["checks"]
    assert got["failed"] == 0 and got["attempted"] == 30
    assert set(got["metrics"]) == {"setup_s", "fits_per_s"}
    assert list(got)[-1] == "checks"


@pytest.mark.parametrize("fault", FAULTS["fit_server"],
                         ids=lambda f: f.__name__)
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    got = run_small("serve_overload")
    assert not got["correct"], got["checks"]
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in got["checks"].values())
    assert np.isfinite(got["metrics"]["setup_s"]["value"])
