import os

# Keep tests on ONE device — only the dry-run uses 512 placeholder devices
# (tests that need fake multi-device spawn subprocesses).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def pytest_sessionfinish(session, exitstatus):
    """On a failing run, dump the process-wide flight recorder so CI can
    attach the black-box bundle (ring events + metrics + component state)
    to the failure artifact."""
    if exitstatus == 0:
        return
    try:
        from repro.obs import flightrec, get_registry

        out = flightrec.dump("results/flight_pytest.json", get_registry(),
                             reason="pytest_failure")
        print(f"\nflight recorder bundle dumped to {out}")
    except Exception as e:  # noqa: BLE001 — never mask the real failure
        print(f"\nflight recorder dump failed: {type(e).__name__}: {e}")
