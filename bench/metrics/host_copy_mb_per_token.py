"""Bytes the engine copies between host and device per output token
(engine host-device copies), in MB (1e6 bytes): every stage of its
``h2d_bytes`` and ``d2h_bytes`` counters over its ``output_tokens``, in the
traced window.  The sizes are reckoned from the arrays copied, not timed."""
from bench import program


def read(run):
    c = program.counts(run)
    if not c or not c.get("output_tokens"):
        return None
    moved = sum(c.get("h2d_bytes", {}).values()) + sum(c.get("d2h_bytes", {}).values())
    return moved / 1e6 / c["output_tokens"]
