"""Host-clock prefill time per thousand prompt tokens, over the prefills
run in the window outside the profiler."""


def read(run):
    t0, t1 = run.window
    steps = [s for s in run.window_steps("prefill", traced=False)]
    tokens = sum(r.prompt_len for r in run.requests
                 if r.admitted is not None and t0 <= r.admitted < t1
                 and any(s.t0 == r.admitted for s in steps))
    if not tokens:
        return None
    return 1e3 * sum(s.seconds for s in steps) / (tokens / 1e3)
