"""Share of the key blocks the index's two launches fetched that came in
ONE copy, because the block's pages lie side by side in the pool (PR 53:
`paged_attention.block_runs`; any other block comes page by page, a 4 KB
copy a page): the sum of `index_blocks_run` over the sum of `index_blocks`
of the traced window's `ptpu.serve.step` spans, the host's mirror of the
launches' walks by the rule their prefetched plane is made by
(`paged_attention_latent.index_blocks_walked`). None for a program that
writes neither, and where no tick selected."""
from benchmark.lib import step_fields


def read(record):
    return step_fields.ratio_percent(record, "index_blocks_run",
                                     "index_blocks")
