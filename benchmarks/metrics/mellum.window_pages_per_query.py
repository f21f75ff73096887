"""Layer ops/sparse_attention, what a window layer's decode query reads:
ring pages its table named (``runner_*_window_pages_read``) over the
decode positions of the window layers (``runner_*_window_positions``),
both summed over the 9 window layers, traced part.  At most 17 (a
1,024-position window over pages of 64 spans 16 or 17), however long the
sequence; a full layer's query reads every page of its sequence, up to
814."""
from benchmarks.harness import readers


def compute(run):
    pages = readers.counter_delta(run, "window_pages_read")
    positions = readers.counter_delta(run, "window_positions")
    if not positions or pages is None:
        return None
    return pages / positions
