"""The benchmark's own light tests: nothing here starts a daemon, loads
libtpu at import or needs the native library."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
