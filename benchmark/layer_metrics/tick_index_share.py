"""Share of device busy time that is self time of the operations under the
four scopes of a learned sparse index (`index_q`: the index queries and the
heads' weights; `index_k`: the index key, its LayerNorm and rope;
`index_scores`: every visible key scored; `index_select`: the exact
selection and its positions), read from the trace
(benchmark/lib/program_trace.py with the scopes of
benchmark/lib/sparse_latent_scopes.py). None where the program writes no
such scope."""
from benchmark.lib import program_trace, sparse_latent_scopes


def read(record):
    return program_trace.scope_share(record,
                                     *sparse_latent_scopes.INDEX) or None
