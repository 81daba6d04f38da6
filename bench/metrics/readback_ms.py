"""Engine: mean duration of the step counters' readback, in ms: the
``engine.finished_slots`` host span, once a round."""
import program_spans


def read(run):
    return program_spans.mean_ms(run, "engine.finished_slots")
