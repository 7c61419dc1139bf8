"""Eqns the lowering executor bound on the host, one eager dispatch each,
per traced decode step: the `eqns` of every `cim.host` island span
(`repro.cim.lower`). A host count carried on a span, not a device
measurement; the executor adds the same count to `cache_stats()
["host_eqns"]`. Eqns a warm resident call skips are not bound and not
counted."""
from bench import spans


def read(run):
    steps = run.traced_decode_steps()
    s = spans.of_run(run)
    if not steps or not s or not s["cim_spans"]:
        return None
    return s["host_eqns"] / len(steps)
