"""Layer ici/rail + ici/block_pool + ici/endpoint: device programs
launched in the traced part of the window per MiB of payload the rail
shipped there (trace count over the ``rail_bytes`` counter's delta)."""
from benchmarks.harness import readers


def compute(run):
    tr = readers.traced(run)
    shipped = readers.counter_delta(run, "rail_bytes")
    if tr is None or not shipped or not tr["trace"]["n_programs"]:
        return None
    return tr["trace"]["n_programs"] / (shipped / 2**20)
