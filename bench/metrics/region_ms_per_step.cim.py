"""Device time of the region programs (programs holding a fused-kernel
call) in the trace, per traced decode step."""


def read(run):
    steps = run.traced_decode_steps()
    region_s = (run.trace or {}).get("region_s")
    if not steps or region_s is None:
        return None
    return 1e3 * region_s / len(steps)
