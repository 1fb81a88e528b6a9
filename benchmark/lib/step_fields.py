"""Sums of the fields the program writes on its `ptpu.serve.step` spans
(`program_trace.of_record(record)["program_spans"]`), for the readers whose
metric is a ratio of two of them over a traced window."""
from __future__ import annotations

from typing import Optional

from . import program_trace


def ratio_percent(record, part: str, whole: str) -> Optional[float]:
    """100 x the sum of field `part` over the sum of field `whole`, over
    the window's step spans that carry both; None where none does (a
    program or a tick that does not write them) or the whole is 0."""
    trace = program_trace.of_record(record)
    if trace is None:
        return None
    fields = [e[3] for e in trace["program_spans"]
              if e[0] == program_trace.STEP and part in e[3]
              and whole in e[3]]
    total = sum(float(f[whole]) for f in fields)
    if not total:
        return None
    return 100.0 * sum(float(f[part]) for f in fields) / total
