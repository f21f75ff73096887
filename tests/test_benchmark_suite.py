"""The benchmark's own tests (``benchmarks/tests``), run from tier-1.

They check the readers the driver's benchmark depends on (stage names,
bvars, the result line, `correct`) against this program at toy size, so a
program change that breaks one of them fails here and not first in the
driver's check.  Each file runs in a child, in the environment
``benchmarks/tests/conftest.py`` names: four virtual CPU devices where this
suite's own conftest has forced eight.  They share nothing but the CPU
and three are nine tenths of the time: all start together, a case waits
for its own.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(f for f in os.listdir(os.path.join(ROOT, "benchmarks", "tests"))
               if f.startswith("test_") and f.endswith(".py"))


@pytest.fixture(scope="module")
def children():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    procs = {name: subprocess.Popen(
        [sys.executable, "-m", "pytest",
         os.path.join("benchmarks", "tests", name), "-q",
         "-p", "no:cacheprovider"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for name in FILES}
    yield procs
    for proc in procs.values():
        if proc.poll() is None:
            proc.kill()
            proc.communicate()


@pytest.mark.parametrize("name", FILES)
def test_benchmark_tests_pass(children, name):
    proc = children[name]
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, out[-4000:] + err[-2000:]
