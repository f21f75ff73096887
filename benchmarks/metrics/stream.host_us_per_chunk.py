"""Layer rpc/stream + ici/stream: self time of ``stream.write``,
``stream.send``, ``stream.on_data``, ``stream.ack`` and
``stream.on_feedback`` per chunk echoed in the traced part, in us (the
credit wait, the handler, the rail and the frame writes under them are
not in it)."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.us_per(
        run, ("stream.write", "stream.send", "stream.on_data",
              "stream.ack", "stream.on_feedback"), "chunk", own=True)
