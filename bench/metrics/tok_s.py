"""Tokens produced in the window over the window's length (host clock)."""
from bench.record import window_tok_s


def read(run):
    return window_tok_s(run)
