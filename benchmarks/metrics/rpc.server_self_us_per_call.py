"""Layer rpc/server dispatch: self time of ``rpc.server.process`` and
``rpc.server.respond`` per completed echo of the traced part, in us
(the handler, the rail and the frame write under them are not in it)."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.us_per(
        run, ("rpc.server.process", "rpc.server.respond"), "echo", own=True)
