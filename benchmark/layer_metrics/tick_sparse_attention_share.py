"""Share of device busy time that is self time of the operations under
scope `paged_attention_sparse`: the gather of the selected cache rows and
the attention over them. None where the program writes no such scope."""
from benchmark.lib import program_trace, sparse_latent_scopes


def read(record):
    return program_trace.scope_share(record,
                                     sparse_latent_scopes.SPARSE) or None
