"""Percent of the prompt tokens the sessions' turns submitted in the window
that the block manager served from cached pages
(`blocks.stats["prefix_hit_tokens"]` over the prompt lengths the driver
submitted): a turn re-sends its whole 32k-64k context, so this says
whether the window held any context's prefill. None for a driver that
keeps no such books."""


def read(record):
    c = record.counters
    if not c.get("prompt_tokens_submitted") or "prefix_hit_tokens" not in c:
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prompt_tokens_submitted"]
