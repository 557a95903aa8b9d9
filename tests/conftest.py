"""Test harness: force a true 8-device virtual-CPU mesh.

Tests never touch an accelerator: XLA_FLAGS must be set before the first
backend init to get eight CPU devices, and the platform is pinned to the
CPU through jax.config so the suite behaves the same whether or not the
caller exported JAX_PLATFORMS=cpu (servers that tests spawn get it in
their environment).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# weedsan: the runtime concurrency sanitizer rides the chaos suites
# when WEED_SANITIZE=1 (the nightly posture) — the plugin is inert
# otherwise. Registered here so it arms BEFORE test modules import the
# package and construct their locks/tasks/sessions.
pytest_plugins = ("seaweedfs_tpu.sanitize.pytest_plugin",)


def pytest_configure(config):
    assert jax.default_backend() == "cpu", jax.default_backend()
    assert len(jax.devices()) == 8, jax.devices()
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); the full "
        "1000-node sweeps and long soaks live here")
