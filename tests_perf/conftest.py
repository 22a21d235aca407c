"""Perf-floor test harness config.

Lives OUTSIDE tests/ on purpose: tests/conftest.py pins JAX to the
virtual CPU mesh, while these floors must run on the chip — run them
there with

    python -m pytest tests_perf -q

Floors are order-of-magnitude backstops: they fail in-round when a
path regresses past ~10x."""

import jax
import pytest

from cilium_tpu import compile_cache

compile_cache.enable()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "perf: order-of-magnitude perf floor (bench env)"
    )


@pytest.fixture(scope="session")
def on_accelerator() -> bool:
    return jax.devices()[0].platform != "cpu"
