"""The rail's device programs' share of the HBM roofline: the bytes an
echo must move (``work.echo_hbm_bytes``: one read and one write of the
payload per direction) over the chip's peak bandwidth, against the time
the caller's chip was busy in the traced part.  Memory-bound by
construction.  In these cells nothing but the rail runs on the chip
during the window, so the busy time is the rail's programs'."""
from benchmarks.harness import readers, work


def compute(run):
    tr = readers.traced(run)
    calls = readers.traced_calls(run)
    if tr is None or not calls or tr["trace"]["busy_s_max"] <= 0:
        return None
    need = sum(work.echo_hbm_bytes(c["bytes"]) for c in calls)
    least_s = need / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["trace"]["busy_s_max"]
