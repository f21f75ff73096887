"""The benchmark's own tests (``benchmarks/tests``), run from tier-1.

They check the readers the driver's benchmark depends on (stage names,
bvars, the result line, `correct`) against this program at toy size, so a
program change that breaks one of them fails here and not first in the
driver's check.  Each file runs in a child, in the environment
``benchmarks/tests/conftest.py`` names: four virtual CPU devices where this
suite's own conftest has forced eight.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(f for f in os.listdir(os.path.join(ROOT, "benchmarks", "tests"))
               if f.startswith("test_") and f.endswith(".py"))


@pytest.mark.parametrize("name", FILES)
def test_benchmark_tests_pass(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join("benchmarks", "tests", name), "-q",
         "-p", "no:cacheprovider"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
