"""Layer psserve/client + rpc/combo_channels: self time of
``ps.client.call`` (the key split, the partitioned fan-out's own work
and the merge; the RPC layer's stages and the parked wait under it taken
out) per call completed in the traced part, in us."""
from benchmarks.harness import spans_ps

KINDS = ("lookup", "update", "resend")


def compute(run):
    return spans_ps.us_per_call(run, ("ps.client.call",), KINDS, own=True)
