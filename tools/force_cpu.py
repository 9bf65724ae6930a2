"""Import this FIRST to run an ad-hoc script on host CPU devices.

Sets JAX_PLATFORMS=cpu and a virtual device count before the first jax
import, which is all this installation needs — same as tests/conftest.py.

Usage:  python -c "import tools.force_cpu; ..."
        (FORCE_CPU_DEVICES=N picks the virtual device count, default 8)
"""
import os

n = os.environ.get("FORCE_CPU_DEVICES", "8")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
