"""Layer ops/sparse_attention, the selection: blocks attended a sparse
position, a layer and K/V head (``runner_*_sparse_selected_blocks`` over
``runner_*_sparse_positions``, traced part).  64 where every position
lies past ``dense_len`` with more than 64 blocks behind it."""
from benchmarks.harness import readers


def compute(run):
    blocks = readers.counter_delta(run, "sparse_selected_blocks")
    positions = readers.counter_delta(run, "sparse_positions")
    if not positions or blocks is None:
        return None
    return blocks / positions
