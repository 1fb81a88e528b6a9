"""Percent of the window's launches of the routed FFN that took the sorted
form's WHOLE form (every rows x top_k pair gathered, multiplied past and
un-sorted) rather than the compact one that moves the held pairs alone,
from the step's own counter. `llama.HELD_ROOM` = 4 at a quarter share gives
the held pairs all rows x top_k places, so 100 is expected until that is
retuned."""


def read(record):
    c = record.counters
    if not c.get("moe_launches"):
        return None
    return 100.0 * c["moe_whole_form"] / c["moe_launches"]
