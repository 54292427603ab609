import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture(scope="session")
def copy(tmp_path_factory):
    """A checkout holding the benchmark with two tiny cells added."""
    from benchmark.tests import tiny

    return tiny.make_copy(str(tmp_path_factory.mktemp("checkout")))
