"""Bootstrap for the benchmark's own tests (run by hand, not by tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

They call the harness's functions at a tiny scale on the CPU backend with
four virtual devices; the command itself (``benchmarks/run.py``) keeps
demanding the chip.  Nothing here yields a time worth writing down.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE"] = "1"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=4"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
