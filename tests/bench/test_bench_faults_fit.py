"""A run of the resident-fit cell whose timed path is broken comes out
not correct: once for each fault the fit can have."""
import pytest

from _cells import run_small  # noqa: E402  (sets sys.path)
from faults import FAULTS  # noqa: E402


def test_a_sound_run_is_correct():
    got = run_small("large_fit")
    assert got["correct"], got["checks"]
    assert got["failed"] == 0 and got["attempted"] > 0
    assert set(got["metrics"]) == {"setup_s", "fit_ms"}
    assert set(got["checks"]) == {"excess_sse", "sse_gap"}


@pytest.mark.parametrize("fault", FAULTS["resident_fit"],
                         ids=lambda f: f.__name__)
def test_a_broken_fit_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    got = run_small("large_fit")
    assert not got["correct"], got["checks"]
