"""Test harness setup.

Forces 8 virtual CPU devices (before the first jax import) so the
mesh/sharding/distributed suites exercise real multi-device code paths
on the single-core CPU host.
"""

import os
import sys

if "jax" not in sys.modules:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
