"""Mean ``serve/decode_step`` span in the window (one step over every row,
ended by the host copy of the sampled tokens)."""
from bench.record import mean_span_ms


def read(run):
    return mean_span_ms(run, "serve/decode_step")
