"""``train/optimizer.fused_apply``'s share of the HBM roofline: the
bytes the updates completed in the traced part must move
(``work_ps.adam_apply_bytes``: the gradients read, each distinct key's
row, ``m``, ``v`` and ``t`` read and written) over the chip's peak
bandwidth, against the device time of the ``jit_ps_adam_apply``
programs there.  Memory-bound by construction."""
from benchmarks.harness import readers, spans_ps, work_ps


def compute(run):
    secs = spans_ps.program_seconds(run, "jit_ps_adam_apply")
    calls = readers.traced_calls(run, "update")
    if not secs or not calls:
        return None
    need = sum(work_ps.adam_apply_bytes(c["n"], c["distinct"],
                                        run["config"]["dim"])
               for c in calls)
    return 100.0 * work_ps.least_seconds(need, run["peaks"]) / secs
