"""Tier-1 runs the benchmark's own arithmetic tests.

``benchmarks/tests/`` is run by hand (its conftest.py says how); a PR that
breaks how a cell is found, how the result line is bounded, a percentile or
the reduction of a device trace would otherwise be seen first by the
driver's chip runs. The four modules taken in here import no jax and take
seconds; each of their cases is collected under its own name, prefixed by
its module's. ``test_faults.py`` and ``test_rehearsal.py`` stay by hand:
they build whole verify programs on XLA:CPU, and conftest.py's rule is five
such builds in tier-1 and no more.
"""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
MODULES = ("test_cells", "test_result_line", "test_stats",
           "test_device_trace")

# the sys.path entries benchmarks/tests/conftest.py sets, plus the tests'
# own directory: the benchmark's modules import each other by bare name
for _p in (os.path.join(BENCH, "tests"), BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
pytest.register_assert_rewrite(*MODULES)

for _name in MODULES:
    _mod = importlib.import_module(_name)
    if os.path.dirname(os.path.abspath(_mod.__file__)) != os.path.join(
            BENCH, "tests"):
        raise ImportError(f"{_name} came from {_mod.__file__}, "
                          "not from benchmarks/tests")
    for _attr, _obj in vars(_mod).items():
        if _attr.startswith("test_") and callable(_obj):
            globals()[f"{_name}__{_attr[len('test_'):]}"] = _obj
