import jax
import pytest


@pytest.fixture
def fresh_programs():
    """Faults are planted at trace time: drop compiled programs around a
    test so that no broken (or sound) program outlives it."""
    from repro.core import driver

    jax.clear_caches()
    driver._clear_compiled()
    yield
    jax.clear_caches()
    driver._clear_compiled()
