import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

# the tests compile small programs on host devices; keep them out of the
# persistent compile cache
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
