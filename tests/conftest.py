"""Shared test config: ``--runslow`` gating for slow tests + seeded RNG."""
import os

import numpy as np
import pytest

# tests (and the launchers they start) run on the CPU, where the Pallas
# kernel runs in interpret mode: a chip, where there is one, stays free for
# chip_smoke.py.  They keep JAX's persistent compilation cache off, so
# nothing a test compiles is written into the checkout.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (subprocess / multi-device end-to-end)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow test: pass --runslow to enable")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.fixture
def rng():
    """Deterministic per-test numpy RNG — reproducible failures."""
    return np.random.default_rng(0xA5EED)


@pytest.fixture(autouse=True)
def _default_launch_configs():
    """Pin the autotuner to the compiled-in defaults for every test: the
    committed CI tuning table must not perturb tests that pinned behavior
    under the default block shapes.  Tests that exercise the table call
    ``set_active_table`` themselves (the teardown re-pins defaults)."""
    from repro.roofline import autotune

    autotune.set_active_table(None)
    yield
    autotune.set_active_table(None)
