"""Router: device idle time per router round, in ms: the idle inside the
``router.round`` host spans that lie wholly within the traced device
activity, over their count (``program_spans``)."""
import program_spans


def read(run):
    got = program_spans.summary(run)
    rounds = (got or {}).get("round_idle_ns")
    return sum(rounds) / len(rounds) * 1e-6 if rounds else None
