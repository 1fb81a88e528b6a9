"""A new kind of driver, added as a file: counts to the traffic's
`count_to` on the device."""
import time

import jax.numpy as jnp

from benchmark.lib.harness import Record, Spans


def run(ctx):
    spans = Spans()
    total = jnp.zeros((), jnp.int32)
    setup_s = time.perf_counter() - ctx.process_start_s
    t0 = time.perf_counter()
    for _ in range(ctx.traffic["count_to"]):
        with spans.span("bench.count"):
            total = (total + 1).block_until_ready()
    return Record(
        correct=int(total) == ctx.traffic["count_to"],
        attempted=ctx.traffic["count_to"], failed=0, setup_s=setup_s,
        samples={}, spans=spans, context=ctx,
        counters={"counted": int(total), "compiles_in_window": 0,
                  "elapsed_s": time.perf_counter() - t0})
