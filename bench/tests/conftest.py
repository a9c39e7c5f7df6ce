"""The benchmark's own tests run on the CPU, with four virtual devices for
the sharded path and Pallas kernels in interpret mode.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH, os.path.join(BENCH, "drivers"),
          os.path.join(BENCH, "metrics")):
    if p not in sys.path:
        sys.path.insert(0, p)
