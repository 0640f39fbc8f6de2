"""Share of the decode lane's active sequences that sat a step out (decode
lane), over the steps in the traced window: over the ``max_batch`` cap or
deferred for want of pages.  The engine's ``decode_left_out_row_steps``
over its ``decode_active_row_steps``."""
from bench import program


def read(run):
    c = program.counts(run)
    if not c or not c.get("decode_active_row_steps"):
        return None
    return 100.0 * c.get("decode_left_out_row_steps", 0) / c["decode_active_row_steps"]
