"""Layer psserve/shard (device to host): time of ``ps.shard.fetch`` (the
pull of the gathered rows to the host, under the shard lock: it waits
for the gather and for whatever the device has queued before it) per
lookup completed in the traced part, in us."""
from benchmarks.harness import spans_ps


def compute(run):
    return spans_ps.us_per_call(run, ("ps.shard.fetch",), ("lookup",),
                                own=False)
