"""Layer ops/sparse_attention, the window layers' decode attention
against its roofline: the least time it can take
(``work_mellum.window_attend_seconds``: the ring pages the counters say
its queries visited, ``runner_*_window_pages_read``, 64 tokens x 2 x 4
heads x 128 x 2 B = 128 KB each, read once at the peak bandwidth, or
scores and values over their keys at the bf16 peak, whichever is longer)
against the device time of the ``window_attend`` kernel calls inside
``jit_runner_hybrid_step`` in the traced part.  Prefill's calls of the
kernel are in neither side."""
from benchmarks.harness import loader, readers, work_mellum

KERNEL = "window_attend"


def compute(run):
    pages = readers.counter_delta(run, "window_pages_read")
    secs = loader.load_metric("sala.sparse_attend_roofline").kernel_seconds(
        run, KERNEL)
    if not pages or secs <= 0:
        return None
    least = work_mellum.window_attend_seconds(run["config"], pages,
                                              run["peaks"])
    return 100.0 * least / secs
