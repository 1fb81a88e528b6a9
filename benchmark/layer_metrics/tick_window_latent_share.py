"""Share of device busy time that is self time of the operations under
scope `paged_attention_latent_window`: the walks of the latent layers that
have a window. None where the program writes no such scope."""
from benchmark.lib import program_trace, sparse_latent_scopes


def read(record):
    return program_trace.scope_share(record,
                                     sparse_latent_scopes.WINDOW) or None
