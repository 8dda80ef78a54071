# NOTE: no XLA_FLAGS here — smoke tests must see the real (single) device.
# Multi-device tests spawn subprocesses that set
# --xla_force_host_platform_device_count themselves.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# property-test modules import these from here
from hypothesis import given, settings, strategies as st  # noqa: E402,F401
