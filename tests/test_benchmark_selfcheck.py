"""The benchmark's own tests (``benchmark/tests``), as tier-1 cases.

They guard the judge: the plain references against the program at toy
size, ``correct`` false for a step that returns its state unchanged or
an altered served token, the span and trace readers. They cannot simply
be collected with ``tests/`` (``benchmark/tests/test_serve.py`` shares a
basename with ``tests/test_serve.py``, and they want a process without
this suite's eight virtual devices), so one module-scoped fixture runs
them once in a subprocess and each of their test functions is one case
here: a failure names the benchmark test that broke.
"""

import ast
import os
import subprocess
import sys
import xml.etree.ElementTree as ElementTree

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK_TESTS = os.path.join(REPO, "benchmark", "tests")
# alone on an idle machine they take 370-430 s (92 cases, PR 37); beside
# five other workers of this suite they have passed 600 s here
TIMEOUT_S = 900


def _test_functions():
    """``(module, function)`` for every test function of
    ``benchmark/tests/test_*.py``, read from the source: nothing is
    imported or run while this suite is collected."""
    found = []
    for fname in sorted(os.listdir(BENCHMARK_TESTS)):
        if not (fname.startswith("test_") and fname.endswith(".py")):
            continue
        with open(os.path.join(BENCHMARK_TESTS, fname)) as f:
            tree = ast.parse(f.read(), fname)
        module = "benchmark.tests." + fname[:-3]
        for node in tree.body:
            if isinstance(node, ast.ClassDef) and node.name.startswith("Test"):
                found += [(f"{module}.{node.name}", sub.name)
                          for sub in node.body
                          if isinstance(sub, ast.FunctionDef)
                          and sub.name.startswith("test")]
            elif (isinstance(node, ast.FunctionDef)
                  and node.name.startswith("test")):
                found.append((module, node.name))
    return found


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    """One run of ``benchmark/tests`` the way its conftest says to run
    them by hand; ``{(classname, case name): what went wrong or None}``
    and the end of the run's output."""
    xml = tmp_path_factory.mktemp("benchmark_selfcheck") / "report.xml"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # tests/conftest.py's eight virtual devices
    try:
        run = subprocess.run(
            [sys.executable, "-m", "pytest", BENCHMARK_TESTS, "-q",
             "-p", "no:cacheprovider", "-p", "no:xdist",
             f"--junitxml={xml}"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=TIMEOUT_S)
        tail = (run.stdout + run.stderr)[-4000:]
    except subprocess.TimeoutExpired as exc:
        return {}, f"benchmark/tests did not finish in {TIMEOUT_S} s: {exc}"
    if not xml.exists():
        return {}, tail
    cases = {}
    for case in ElementTree.parse(xml).getroot().iter("testcase"):
        bad = [child for child in case
               if child.tag in ("failure", "error", "skipped")]
        cases[(case.get("classname"), case.get("name"))] = (
            f"{bad[0].tag}: {bad[0].get('message')}\n{bad[0].text}"
            if bad else None)
    return cases, tail


_FUNCTIONS = _test_functions()


@pytest.mark.parametrize(
    "classname,function", _FUNCTIONS,
    ids=[f"{c.rsplit('.', 1)[-1]}::{f}" for c, f in _FUNCTIONS])
def test_benchmark_test_passes(report, classname, function):
    cases, tail = report
    mine = {name: wrong for (cls, name), wrong in cases.items()
            if cls == classname
            and (name == function or name.startswith(function + "["))}
    assert mine, (f"no case of {classname}::{function} in the report of "
                  f"benchmark/tests:\n{tail}")
    wrong = {name: why for name, why in mine.items() if why is not None}
    assert not wrong, "\n\n".join(f"{name}: {why}"
                                  for name, why in wrong.items())
