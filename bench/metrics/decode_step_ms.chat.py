"""Mean host-clock time of a decode step in the window, timed around
`block_until_ready`; steps run under the profiler are left out."""
from bench.record import mean_step_ms


def read(run):
    return mean_step_ms(run, "decode")
