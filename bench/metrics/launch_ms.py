"""Mean ``serve/decode_launch`` span in the window: the host's call into the
compiled decode step, until it returns with the step enqueued."""
from bench.record import mean_span_ms


def read(run):
    return mean_span_ms(run, "serve/decode_launch")
