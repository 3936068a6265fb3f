"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Multi-chip TPU hardware is unavailable in CI; sharding tests run against
8 virtual CPU devices (the XLA host-platform device-count trick), mirroring
how the reference tests multi-node behavior without real clusters (kubemark
hollow nodes, SURVEY.md §4). Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take a chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
