"""Device idle time whose innermost span is a `cim.host` island (the
lowering executor binding host eqns one at a time), per traced decode
step."""
from bench import spans


def read(run):
    steps = run.traced_decode_steps()
    s = spans.of_run(run)
    if not steps or not s or not s["cim_spans"]:
        return None
    return 1e3 * dict(s["idle_by_span"]).get("cim.host", 0.0) / len(steps)
