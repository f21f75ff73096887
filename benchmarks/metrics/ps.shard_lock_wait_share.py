"""Layer psserve/shard: time of ``ps.shard.lock_wait`` (the wait for the
one ``psserve.shard_apply`` lock every handler thread shares) over the
time of all the shard's stages (the wait, the gather's dispatch, the
device-to-host pull, the apply's dispatch, the hot-key note), in
percent."""
from benchmarks.harness import spans_ps


def compute(run):
    total = sum(s.dur for s in spans_ps.spans(run, spans_ps.SHARD_STAGES))
    if not total:
        return None
    waited = sum(s.dur for s in spans_ps.spans(run, ("ps.shard.lock_wait",)))
    return 100.0 * waited / total
