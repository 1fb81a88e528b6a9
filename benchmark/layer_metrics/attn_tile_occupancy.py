"""Share of the token rows the mixed launch of paged attention computed
that held a query token: the sum of `attn_rows_live` over the sum of
`attn_rows_packed` (the rows of the tiles the work items ran on) of the
traced window's mixed ticks; a decode tick carries neither field. None
where no traced tick is mixed."""
from benchmark.lib import step_fields


def read(record):
    return step_fields.ratio_percent(record, "attn_rows_live",
                                     "attn_rows_packed")
