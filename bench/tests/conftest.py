"""The harness's own tests.  Run them by path: ``python -m pytest
bench/tests``.  On the CPU the host backend shows four devices, for the
batch driver's mesh path."""
import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE),
          os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
