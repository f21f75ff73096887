"""Layer serving/engine: mean length of the ``serve.engine.step`` stage
in the traced part (the runner's step as the engine sees it: table
translation, dispatch, the device program and the fetch of its tokens),
in ms."""
from benchmarks.harness import spans_sala


def compute(run):
    return spans_sala.mean_ms(run, "serve.engine.step")
