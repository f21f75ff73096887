"""Layer ops/latent_attention, the ``latent_attend`` kernel in the decode
step against its roofline: the least time attention over the latent
pages can take (``work_glm.latent_attend_seconds``: every DISTINCT live
page read once a layer a step, 73.7 KB each, or scores and values of
every row attended, whichever is longer) against the device time of the
kernel's calls inside ``jit_runner_hybrid_step`` in the traced part.  A
page that 16 slots share counts once: what a kernel that reads it once a
slot leaves on the table shows here."""
from benchmarks.harness import loader, readers, work_glm

KERNEL = ("latent_attend",)


def compute(run):
    pages = readers.counter_delta(run, "latent_pages_distinct")
    rows = readers.counter_delta(run, "latent_tokens_read")
    secs = loader.load_metric("glm.expert_ffn_roofline").kernel_seconds(
        run, KERNEL)
    if not pages or rows is None or secs <= 0:
        return None
    least = work_glm.latent_attend_seconds(run["config"], pages, rows,
                                           run["peaks"])
    return 100.0 * least / secs
