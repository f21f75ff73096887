"""Layer serving/engine: of the decode steps of the traced part (one
``serve.engine.step`` stage each), the share dispatched while another
step was still in flight: the stage's ``ahead`` stamp, in percent.  None
where no stage carries the stamp (a program whose loop waits for every
step before it builds the next)."""
from benchmarks.harness import spans_sala


def compute(run):
    spans = (spans_sala.load(run) or {}).get("serve.engine.step") or ()
    stamped = [int(st["ahead"]) for _d, st in spans if "ahead" in st]
    if not stamped:
        return None
    return 100.0 * sum(1 for a in stamped if a) / len(stamped)
