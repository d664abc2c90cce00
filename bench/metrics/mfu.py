"""Model FLOPs of the window's prefills and decoded tokens over the time in
their spans at the chip's bf16 peak (``bench/flops.py``, ``peaks.json``)."""
from bench.record import serve_mfu


def read(run):
    return serve_mfu(run)
