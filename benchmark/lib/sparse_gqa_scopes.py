"""The named scopes of a tick whose layers attend over heads' own keys and
values under a learned sparse index (PR 50, Keye-VL-2.0): the SAME names
dots3-note's ops write for the same operations (`sparse_latent_scopes`:
`index_q`, `index_k`, `index_scores`, `index_select`,
`paged_attention_sparse`), so one registration and one set of scope-share
readers serve both; the dense walk of the rows that had no selection to
make stays under `paged_attention` itself. What differs is which scopes
make up the attention sub-block of this model: no latent ones.
"""
from __future__ import annotations

from . import sparse_latent_scopes

INDEX = sparse_latent_scopes.INDEX
SCORES = sparse_latent_scopes.SCORES
SELECT = sparse_latent_scopes.SELECT
SPARSE = sparse_latent_scopes.SPARSE
ATTENTION = ("qkv", "cache_write", "paged_attention", "attn_out", *INDEX,
             SPARSE)
MOE = sparse_latent_scopes.MOE
register = sparse_latent_scopes.register
