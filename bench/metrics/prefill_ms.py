"""Mean ``serve/prefill`` span in the window (one B=1 prompt's forward and
the copy of its first token)."""
from bench.record import mean_span_ms


def read(run):
    return mean_span_ms(run, "serve/prefill")
