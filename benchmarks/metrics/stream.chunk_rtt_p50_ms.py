"""Layer rpc/stream + ici/stream: median time from the write of a chunk
to the receipt of its echo (benchmark-side clock), over the chunks
echoed in the traced part."""
from benchmarks.harness import readers


def compute(run):
    return readers.call_latency_p50_ms(run, "chunk")
