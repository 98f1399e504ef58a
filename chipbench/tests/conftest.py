"""CPU checks of the benchmark: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.

Four virtual CPU devices stand in for the four-chip cell's mesh, and the
persistent compile cache goes to a temporary directory, not the
checkout's.  Both are set before JAX is first imported.
"""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="chipbench-test-cache-"))

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
