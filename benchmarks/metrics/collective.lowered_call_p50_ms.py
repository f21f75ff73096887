"""Layer ici/collective + rpc/combo_channels: median time of the
lowered 4-way fan-out call (benchmark-side clock around
``ParallelChannel.call_sync`` and ``block_until_ready``)."""
from benchmarks.harness import readers


def compute(run):
    return readers.call_latency_p50_ms(run, "fanout")
