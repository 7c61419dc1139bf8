"""Share of the region programs' device time that their modelled work
needs at the chip's HBM bandwidth, in traced decode steps.

The modelled work is the ledger's compute word-ops (`words32`) those steps
charged, at 12 bytes each: two operand rows read and one result row written
per access, 4 bytes a word. It is bound by memory: bit-plane accesses do no
arithmetic a TPU's units could be short of. A layout that keeps planes
on-chip between accesses would need less than this count."""


def read(run):
    steps = run.traced_decode_steps()
    region_s = (run.trace or {}).get("region_s")
    if not steps or not region_s or not run.peaks:
        return None
    words = sum(s.words32 for s in steps)
    if words <= 0:
        return None
    least_s = 12.0 * words / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / region_s
