"""The per-test time limit (tests/conftest.py ``TEST_TIME_LIMIT_S``): a test
that waits past it fails by name with the traceback of where it waited, and
the run goes on."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WAITING_FILE = '''
import time

import tests.conftest as conftest

conftest.TEST_TIME_LIMIT_S = 0.5  # read when each test's limit is armed


def test_waits():
    time.sleep(60)


def test_next():
    pass
'''


def test_a_waiting_test_fails_by_name_and_the_run_goes_on(tmp_path):
    path = tmp_path / "test_waiting.py"
    path.write_text(WAITING_FILE)
    # a file outside the repo does not pick tests/conftest.py up by itself:
    # load it as the plugin it is
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(path), "-q", "-p",
         "tests.conftest", "-p", "no:cacheprovider", "--rootdir",
         str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert ("test_waiting.py::test_waits waited past the per-test limit "
            "of 0.5 s") in out, out
    assert "time.sleep(60)" in out, out  # where it waited
