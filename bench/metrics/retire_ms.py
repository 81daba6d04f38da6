"""Router: mean duration of a retirement, in ms: the ``router.retire``
host span (decode dispatch of the finished slots and their release), in
rounds where a slot finished."""
import program_spans


def read(run):
    return program_spans.mean_ms(run, "router.retire")
