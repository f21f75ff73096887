"""Layer serving/service + serving/engine: median time from the issue
of a request to its first token at the client, over the requests whose
first token came in the traced part."""
from benchmarks.harness import readers, stats


def compute(run):
    tr = readers.traced(run)
    if tr is None:
        return None
    ttft = [(c["times"][0] - c["t_issue"]) * 1e3
            for c in run["records"]["calls"]
            if c.get("times") and tr["t0"] <= c["times"][0] <= tr["t1"]]
    return stats.percentile(ttft, 50.0) if ttft else None
