"""Copy-on-write page copies the engine executed per tick of the window
(`stats["cow_block_copies"]` over its steps): each call of
`engine._copy_blocks` selects over the whole page pool."""


def read(record):
    c = record.counters
    if "cow_block_copies" not in c or not c["engine_steps"]:
        return None
    return c["cow_block_copies"] / c["engine_steps"]
