"""The benchmark's own tests run on the CPU, on four virtual devices (the
``dp=4`` layout), at tiny sizes.  They print no device metric.  Run with
``python -m pytest benchmark/tests -q`` from the root of the repo."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
