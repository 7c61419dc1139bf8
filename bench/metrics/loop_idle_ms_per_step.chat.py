"""Device idle time while the engine's loop was admitting, inserting a
prefilled request, reading tokens back and retiring, or waiting for an
arrival (`serve.admit`, `serve.insert`, `serve.emit`, `serve.wait`, by
the innermost `serve.*` span), per decode step in the traced window."""
from bench import spans


def read(run):
    steps = [x for x in run.steps if x.traced and x.kind == "decode"]
    s = spans.of_run(run)
    if not steps or not s or not s["loop_idle_s"]:
        return None
    return 1e3 * sum(s["loop_idle_s"].values()) / len(steps)
