"""Model FLOPs of the window's decoded tokens (from the configuration's
shapes, attention over each token's context included) over the window's
length times the chip's peak bf16 rate."""
from bench.record import window_mfu


def read(run):
    return window_mfu(run)
