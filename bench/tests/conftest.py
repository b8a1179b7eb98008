import os
import sys

# the benchmark's tests run on the CPU (kernels in interpret mode), leave no
# compile cache behind, and import the benchmark's modules and the program
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
