"""Test configuration: force an 8-device virtual CPU mesh before jax import.

Mirrors the reference's test-cluster approach (ES spins up multi-node
ElasticsearchIntegrationTest clusters); we spin up 8 virtual XLA CPU
devices so multi-shard Mesh/shard_map paths are exercised without TPUs.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_ivf_cache():
    """The IVF blob cache is process-global (content-addressed, so safe for
    correctness) — but a Node(data_path=...) in one test must not leave its
    durable tier configured for the next test's ephemeral nodes."""
    from elasticsearch_tpu.index import ivf_cache

    ivf_cache.reset()
    yield
    ivf_cache.reset()


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
