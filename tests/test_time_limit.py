"""The per-test time limit of tests/conftest.py: a test that hangs fails
by name at the limit instead of taking the whole run with it."""
import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))


def test_a_sleeping_test_fails_with_its_name(tmp_path):
    # the suite's own conftest with the limit lowered, a sleeper beside it
    with open(os.path.join(TESTS, "conftest.py")) as f:
        (tmp_path / "conftest.py").write_text(
            f.read() + "\n_TEST_LIMIT_S = 1.0\n")
    (tmp_path / "test_sleeper.py").write_text(
        "import time\n\n\n"
        "def test_sleeps():\n    time.sleep(120)\n\n\n"
        "def test_returns():\n    pass\n")
    env = dict(os.environ, PYTHONPATH=os.path.dirname(TESTS))
    # a session of its own: it owns (and removes) its compile cache
    env.pop("PYTEST_XDIST_WORKER", None)
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "--rootdir", str(tmp_path), str(tmp_path)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=240)
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "test_sleeper.py::test_sleeps ran over the 1 s a test may " \
        "take" in out, out
    assert "time.sleep(120)" in out, out      # where it stood
