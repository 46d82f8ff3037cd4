"""Import repo tools/*.py modules from inside the package, a tool or a test.

The operator toolbox (tools/trace_summary.py, trace_merge.py,
fleet_scrape.py, ...) is deliberately stdlib-only and lives OUTSIDE the
package so it runs on boxes that can't import jax. Harness code that wants
to reuse a tool in-process (the e2e runner's fleet scraper, a test that
needs a tool's fake) imports it through this one helper instead of each
hand-rolling the sys.path dance.
"""

from __future__ import annotations

import importlib
import os
import sys

TOOLS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), "tools")


def load_tool(name: str):
    """Import ``tools/<name>.py`` as a module (tools is not a package)."""
    sys.path.insert(0, TOOLS_DIR)
    try:
        return importlib.import_module(name)
    finally:
        sys.path.remove(TOOLS_DIR)
