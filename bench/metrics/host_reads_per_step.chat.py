"""Device-to-host reads that block the engine's loop (one per token: the
first token after a prefill, each slot's token after a decode step), per
decode step: the engine's `host_reads` counter as each traced
`serve.decode` span carries it, from the first to the last of them. A host
count carried on a span, not a device measurement."""
from bench import spans


def read(run):
    s = spans.of_run(run)
    return s.get("host_reads_per_step") if s else None
