"""Tokens produced in the window over the window's length (host clock), in
an open loop offered more than the server completes: its capacity at the
mix's lengths, prefills and the engine's loop included."""
from bench.record import window_tok_s


def read(run):
    return window_tok_s(run)
