"""Layer src/cc/net upcall lane: median ``queue_wait_us`` over
``rpc.server.process`` and ``rpc.client.on_response`` — the native
core's own sample of the time from a frame's cut out of the read buffer
to the stage's first line (executor queue, the hop onto the worker, the
wait for the interpreter lock, the decode of the meta)."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.stat_p50(
        run, ("rpc.server.process", "rpc.client.on_response"),
        "queue_wait_us")
