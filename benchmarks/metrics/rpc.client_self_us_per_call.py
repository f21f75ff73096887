"""Layer rpc/channel: self time of the client's stages per completed echo
of the traced part, in us: ``rpc.client.call`` (entry of the call to the
reply in the caller's hands, less the rail, the frame write and the
parked wait under it) and ``rpc.client.on_response`` (the upcall that
completes the call, less the claim under it)."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.us_per(
        run, ("rpc.client.call", "rpc.client.on_response"), "echo", own=True)
