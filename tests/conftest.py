import os
import sys

# tests run single-device (the dry-run sets its own 512-device flag in a
# subprocess; multi-device TP tests spawn subprocesses with their own flags)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--chaos-seed", type=int, default=0,
                     help="base seed for the elastic-serving chaos schedule "
                          "sweep (tests/test_elastic_serving.py); pair with "
                          "CHAOS_SCHEDULES=<n> to resize the sweep")


@pytest.fixture(scope="session")
def mesh1():
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
