"""CPU tests of the harness's own arithmetic. Run by hand from the root of
the checkout (42 cases, under a minute with ``-n 6 --dist loadfile``). They
are not yet part of the repository's tier-1 tests: that takes a file under
``tests/``, which a ``benchmark`` PR may not add (``PERF.md`` section 7). No
test file here imports this one by name, so a collector under ``tests/``,
which has a ``conftest.py`` of its own, can take them as they are:

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
