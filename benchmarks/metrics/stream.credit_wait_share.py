"""Layer rpc/stream flow control: time of ``stream.credit_wait`` (the
writer parked on a full window) over that of ``stream.write``, in
percent."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.share_percent(run, "stream.credit_wait",
                                       "stream.write")
