"""Router: mean duration of an admission, in ms: the ``router.admit``
host span (tier choice, two text encodes, the admit dispatch and their
copies to the device)."""
import program_spans


def read(run):
    return program_spans.mean_ms(run, "router.admit")
