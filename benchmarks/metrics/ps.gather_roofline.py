"""The gather's share of the HBM roofline: the bytes the lookups
completed in the traced part must move (``work_ps.gather_bytes``: each
row read once and written once) over the chip's peak bandwidth, against
the device time of the ``jit_ps_gather`` programs there.  Memory-bound
by construction; padding to a key bucket and to a batch is the
program's cost, not the work's."""
from benchmarks.harness import readers, spans_ps, work_ps


def compute(run):
    secs = spans_ps.program_seconds(run, "jit_ps_gather")
    calls = readers.traced_calls(run, "lookup")
    if not secs or not calls:
        return None
    need = sum(work_ps.gather_bytes(c["n"], run["config"]["dim"])
               for c in calls)
    return 100.0 * work_ps.least_seconds(need, run["peaks"]) / secs
