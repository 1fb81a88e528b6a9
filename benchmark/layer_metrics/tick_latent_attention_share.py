"""Share of device busy time that is self time of the operations under the
four scopes of a latent-attention sub-block (`latent_q`: Wqa, its norm,
Wqb and the absorption through Wkvb's key half; `latent_kv`: Wkva, its
norm, rope; `paged_attention_latent`: the launches over the latent pages;
`latent_out`: Wkvb's value half and Wo), read from the trace
(benchmark/lib/program_trace.py with the scopes of
benchmark/lib/latent_scopes.py). None where the program writes no such
scope."""
from benchmark.lib import latent_scopes, program_trace


def read(record):
    return program_trace.scope_share(record, *latent_scopes.LATENT) or None
