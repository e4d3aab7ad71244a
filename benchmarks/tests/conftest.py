"""CPU tests of the harness's own arithmetic. Run by hand from the root of
the checkout (they are not part of the repository's tier-1 tests):

  JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH, os.path.join(BENCH, "lib"),
          os.path.join(BENCH, "engines")):
    if p not in sys.path:
        sys.path.insert(0, p)
