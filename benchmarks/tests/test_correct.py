"""The control and the faults, at a size a test run can hold: each
drives the rest of a run (everything but the harness's look for a chip)
with the timed path broken underneath and sees ``correct`` come out
false; the sound path comes out true with a well-formed last line."""
import json
import subprocess
import sys

import pytest

from benchmarks.harness import loader

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _well_formed(r, cell_name, traced):
    assert RESULT_KEYS <= set(r)
    assert list(r)[-1] == "compared"
    cell = loader.load_cell(cell_name, bench=loader.load_benchmark_with_staged())
    names = {m["name"] for m in
             (cell.per_layer if traced else cell.end_to_end)}
    assert set(r["metrics"]) <= names
    for m in r["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    for c in r["compared"].values():
        assert set(c) == {"value", "limit"}
    if traced:
        assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
        assert len(r["breakdown"]["device_ops"]) <= 10
    else:
        assert "setup_s" in r["metrics"] and len(r["metrics"]) >= 2


@pytest.mark.parametrize("cell", ["echo_ladder", "stream_4m", "rail_x4"])
def test_sound_run_is_correct_and_well_formed(run_toy, cell):
    r = run_toy(cell)
    _well_formed(r, cell, traced=False)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0


def _over(r):
    return {n for n, c in r["compared"].items() if c["value"] > c["limit"]}


def test_staged_chat_decode_is_held_back_by_compiles_in_window_alone(run_toy):
    """Why ``chat_decode`` is staged and not a cell (PERF.md, Open
    questions 1): the program's ``PagePool.arena()`` recompiles its
    restack inside the window, and nothing else is over its limit."""
    import jax
    jax.clear_caches()      # earlier tests may have met every pattern
    r = run_toy("chat_decode")
    _well_formed(r, "chat_decode", traced=False)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["correct"] is False and _over(r) == {"compiles_in_window"}


def test_staged_chat_decode_is_correct_once_the_zero_row_is_committed(
        run_toy, monkeypatch):
    """The second witness: with the unleased rows' zero buffer committed
    to the pool's device (the one-line fix the program needs; patched in
    from outside, for this test only) nothing compiles in the window."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.kvcache import pages
    arena = pages.PagePool.arena

    def arena_with_committed_zero_row(self):
        if self._zero_row is None:
            self._zero_row = jax.device_put(
                jnp.zeros((self.pages_per_block * self.page_bytes,),
                          jnp.uint8), self.pool.device)
        return arena(self)
    monkeypatch.setattr(pages.PagePool, "arena",
                        arena_with_committed_zero_row)
    jax.clear_caches()
    r = run_toy("chat_decode")
    assert r["correct"] is True, r["compared"]


@pytest.mark.parametrize("cell", ["echo_ladder", "chat_decode"])
def test_traced_run_reports_per_layer_metrics(run_toy, cell):
    r = run_toy(cell, trace=True, seconds=2.0)
    _well_formed(r, cell, traced=True)
    assert r["metrics"]


@pytest.mark.parametrize("cell,control,number", [
    # the control: the reference (an identity) in the program's place
    # breaks the guarantee "the reply is in a buffer of its own"
    ("echo_ladder", "identity", "aliased_replies"),
    ("stream_4m", "identity", "aliased_replies"),
    ("rail_x4", "identity", "aliased_replies"),
    # the fault: an answer altered where it is produced
    ("echo_ladder", "altered_reply", "mismatched_words"),
    ("stream_4m", "altered_reply", "mismatched_words"),
    ("rail_x4", "altered_reply", "mismatched_words"),
    # the fault: the exchange between chips left out
    ("rail_x4", "no_exchange", "echo_moves_missing"),
    # ... and left out for two of the three servers only
    ("rail_x4", "misplaced_server", "echo_moves_missing"),
    ("rail_x4", "misplaced_server", "unexpected_same_chip_copies"),
    # the fault: a token altered where it is produced
    ("chat_decode", "altered_token", "served_logit_gap_max"),
])
def test_broken_timed_path_is_not_correct(run_toy, cell, control, number):
    r = run_toy(cell, control=control)
    assert r["correct"] is False
    c = r["compared"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell_args", [["--workload", "echo_ladder"],
                                       ["--workload", "chat_decode",
                                        "--staged"]])
def test_run_refuses_a_cpu(tmp_path, cell_args):
    """The command itself, on a machine with no TPU: exit code other
    than 0 and no result line (a staged cell is found with ``--staged``
    only)."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *cell_args,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=loader.ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
