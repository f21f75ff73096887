"""The benchmark's own tests: quick, CPU only.  Run them with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 -m pytest benchmarks/tests -q

Nothing here loads the TPU library; the persistent compile cache is off
so that a test run leaves nothing behind.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

TOY_PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
             "ici_bytes_per_s": 200e9}


@pytest.fixture(scope="session")
def toy_sizes():
    with open(os.path.join(DATA, "toy.json")) as f:
        return json.load(f)


@pytest.fixture
def toy_cell(toy_sizes):
    """A cell of BENCHMARK.json cut to toy size (tests only)."""
    from benchmarks.harness import loader

    def make(name):
        cell = loader.load_cell(name, bench=loader.load_benchmark_with_staged())
        cell.config.update(toy_sizes.get(cell.config_name, {}))
        cell.traffic.update(toy_sizes.get(cell.traffic_name, {}))
        return cell
    return make


@pytest.fixture
def run_toy(toy_cell, monkeypatch):
    """Drive the rest of a run (everything but the look for a chip) on
    the CPU devices; returns the parsed result line."""
    import io
    import time

    def run(name, control=None, trace=False, seconds=1.5, seed=2**31 + 77):
        import jax
        from benchmarks import run as runmod
        monkeypatch.setattr(runmod, "setup_compile_cache", lambda: "(off)")
        cell = toy_cell(name)
        if len(jax.devices()) < cell.chips:
            pytest.skip(f"needs {cell.chips} (virtual) devices")
        out = io.StringIO()
        rc = runmod.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                             devices=jax.devices()[:cell.chips],
                             peaks=TOY_PEAKS, t_start=time.monotonic(),
                             control=control, stdout=out)
        assert rc == 0
        lines = out.getvalue().strip().splitlines()
        assert len(lines) == 1
        return json.loads(lines[0])
    return run
