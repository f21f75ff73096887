"""Layer psserve/service + serving/batcher: self time of
``ps.server.lookup``, ``ps.server.update`` (the handlers and the batched
lookup's completion) and ``ps.batcher.run`` (a batch on the drainer's
thread: formation, scatter of the rows) per call completed in the traced
part, in us; the shard's stages, the reply's write and the RPC layer's
stages under them are not in it."""
from benchmarks.harness import spans_ps

KINDS = ("lookup", "update", "resend")


def compute(run):
    return spans_ps.us_per_call(run, spans_ps.SERVER_STAGES, KINDS, own=True)
