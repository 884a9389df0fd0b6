"""Golden-trajectory regression tier: small deterministic runs pinned
against committed fixtures (tests/golden/*.json, regenerated only by
``python tests/golden/regen.py``).

Assertions per the regression contract:
  * flat engines (flat_xla, flat_scenario, flat_int8_ef21) reproduce
    their fixture BIT-EXACTLY (fixtures are recorded on the jax version
    the repo runs on, which the fixture names);
  * the seed vmap engine reproduces its fixture the same way;
  * cross-engine (flat vs the seed vmap trajectory) stays <= 1e-5 —
    the engine-parity envelope the repo has tested since PR 1.
"""
import numpy as np
import pytest

from _golden_common import CASES, load_fixture, run_case

TRACE_KEYS = ("loss", "loss_last_step", "eta_mean")


def _assert_trace(got, fixture):
    for k in TRACE_KEYS + ("params_l2",):
        np.testing.assert_array_equal(np.asarray(got[k], np.float32),
                                      np.asarray(fixture[k], np.float32),
                                      err_msg=k)


@pytest.fixture(scope="module")
def traces():
    return {name: run_case(name) for name in CASES}


@pytest.mark.parametrize("name", list(CASES))
def test_golden_trajectory(name, traces):
    _assert_trace(traces[name], load_fixture(name))


def test_cross_engine_envelope(traces):
    """flat engine vs the seed vmap engine on the IDENTICAL run: the
    1e-5 parity envelope (same protocol as the PR 1 parity tests, now
    pinned against the committed seed trajectory)."""
    vmap_fix = load_fixture("seed_vmap")
    for k in TRACE_KEYS:
        np.testing.assert_allclose(
            np.asarray(traces["flat_xla"][k], np.float32),
            np.asarray(vmap_fix[k], np.float32),
            rtol=1e-5, atol=1e-5, err_msg=k)
