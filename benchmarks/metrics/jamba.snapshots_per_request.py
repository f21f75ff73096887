"""Layer kvcache/layered, the snapshot policy: state snapshots taken
(``kvcache_*_state_snapshots``: a 10 MB row copied where a prompt's
prefill stands at its last page boundary) over requests completed, in
the traced part.  Every prompt of this cell ends in a message nobody
sends again: 1.0 is a copy a request that no admission will hit, 0 a
policy that skips them (or a cache with no row to spare: see
``state_snapshot_no_row`` in the run's log)."""
from benchmarks.harness import readers


def compute(run):
    taken = readers.counter_delta(run, "state_snapshots")
    done = len(readers.traced_calls(run, "generate"))
    if taken is None or not done:
        return None
    return taken / done
