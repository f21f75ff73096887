"""Layer kvcache/layered: time of the ``kvcache.state.restore`` stage
(the snapshot's copy into the sequence's state row, 25 MB, dispatched
at admission) per request completed in the traced part, in ms."""
from benchmarks.harness import readers, spans_sala


def compute(run):
    total = spans_sala.total_ms(run, "kvcache.state.restore")
    n = len(readers.traced_calls(run, "generate"))
    if total is None or not n:
        return None
    return total / n
