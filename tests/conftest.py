import os
import sys

# Tests run single-host with a handful of virtual CPU devices for the
# distributed paths.  The 512-device setting is reserved for the dry-run
# (launch/dryrun.py) and must NOT leak here.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import compat  # noqa: E402

import pytest  # noqa: E402


# every mesh is built as the program builds its own: Auto axes (JAX's own
# make_mesh defaults to Explicit ones)
@pytest.fixture(scope="session")
def mesh22():
    return compat.make_mesh((2, 2), ("data", "model"))


@pytest.fixture(scope="session")
def mesh_stage4():
    return compat.make_mesh((4,), ("stage",))


@pytest.fixture(scope="session")
def mesh11():
    return compat.make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="session")
def mesh_pod():
    return compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
