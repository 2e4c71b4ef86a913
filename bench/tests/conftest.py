"""Fixtures of the benchmark's tests."""
import pytest

import benchkit  # noqa: F401  (puts the checkout on the import path)


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices("cpu")
