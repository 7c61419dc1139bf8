"""Device time of every other program in the trace (the lowering's host
islands, run op by op, and the sampling between steps), per traced decode
step."""


def read(run):
    steps = run.traced_decode_steps()
    other_s = (run.trace or {}).get("other_s")
    if not steps or other_s is None:
        return None
    return 1e3 * other_s / len(steps)
