"""Layer serving/engine, admissions against decoding: device time of
``jit_runner_hybrid_prefill`` (every request's uncached tail or snippet
in chunks of at most 512 positions through the window rings, the full
layers' pages and the experts, and the tail behind its last page
boundary as one chunk more) over the device's busy time in the traced
part, in percent: the share of the chip that the decode steps of the
other 31 slots wait for."""
from benchmarks.harness import loader


def compute(run):
    # the same share of the same program as the other models' readers
    return loader.load_metric("glm.prefill_device_share").compute(run)
