"""The time limit that ``tests/conftest.py`` puts on every test: a test that
runs past it is reported failed, with its stacks, and holds up nothing
after it.  A child ``pytest`` runs a stuck test under the same hooks with
a limit of a few seconds."""

import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
LIMIT_S = 3


def _child(tmp_path, *options):
    """Run a child pytest in ``tmp_path`` on one file: a test that sleeps
    far past the limit, then one that passes."""
    (tmp_path / "conftest.py").write_text(
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location("
        f"'zoo_conftest', {os.path.join(HERE, 'conftest.py')!r})\n"
        "zoo = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(zoo)\n"
        f"pytest_runtest_protocol = zoo.time_limit({LIMIT_S})\n"
        "pytest_xdist_make_scheduler = zoo.pytest_xdist_make_scheduler\n")
    (tmp_path / "test_stuck.py").write_text(
        "import pathlib, time\n"
        "def test_stuck():\n"
        "    pathlib.Path('started').write_text(repr(time.time()))\n"
        "    time.sleep(120)\n"
        "def test_after():\n"
        "    pass\n")
    child = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", *options, "test_stuck.py"],
        cwd=tmp_path, capture_output=True, text=True, timeout=100)
    return child, time.time() - float((tmp_path / "started").read_text())


def test_a_stuck_test_fails_at_the_limit(tmp_path):
    child, took = _child(tmp_path, "-p", "no:xdist")
    assert child.returncode != 0, child.stdout
    assert f"Timeout (0:00:0{LIMIT_S})!" in child.stderr, child.stderr
    assert "in test_stuck" in child.stderr, child.stderr
    assert "passed" not in child.stdout, child.stdout
    assert LIMIT_S <= took < LIMIT_S + 5, took


def test_under_xdist_the_rest_of_the_file_runs(tmp_path):
    """The worker goes down and the stuck test is reported failed once;
    a new worker runs the test after it."""
    child, took = _child(tmp_path, "-p", "xdist", "-n", "1",
                         "--dist", "loadfile")
    assert child.returncode == 1, child.stdout
    assert "1 failed, 1 passed" in child.stdout, child.stdout
    assert "crashed while running 'test_stuck.py::test_stuck'" \
        in child.stdout, child.stdout
    assert f"Timeout (0:00:0{LIMIT_S})!" in child.stderr, child.stderr
    assert took < 60, took
