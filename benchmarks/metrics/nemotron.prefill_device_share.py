"""Layer serving/engine, admissions against decoding: device time of
``jit_runner_hybrid_prefill`` (every request's message in chunks of at
most 512 positions through the chunk scan and the held experts, and the
tail behind its last page boundary as one chunk more) over the device's
busy time in the traced part, in percent: the share of the chip that
the decode steps of the other 63 slots wait for."""
from benchmarks.harness import loader


def compute(run):
    # the same share of the same program as the other model's reader
    return loader.load_metric("glm.prefill_device_share").compute(run)
