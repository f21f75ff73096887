"""Layer psserve/shard (hot keys): time of ``ps.shard.note_hot`` (the
hot-key bookkeeping a served lookup owes the shard: once a member on the
batcher's thread, or once a batch over all its live keys) per lookup
completed in the traced part, in us."""
from benchmarks.harness import spans_ps


def compute(run):
    return spans_ps.us_per_call(run, ("ps.shard.note_hot",), ("lookup",),
                                own=False)
