"""`tick_index_share` of a tick whose layers attend over heads' own keys
and values (PR 50): the share of device busy time that is self time under
the four scopes of the learned sparse index (`index_q`, `index_k`,
`index_scores`, `index_select`), which such a layer's ops write under the
names dots3-note's write, so the accepted reader reads them; an entry of
its own because that reader's cell list is the latent cell's alone
(benchmark/tests/test_sparse_latent.py). None where the program writes no
such scope."""
from benchmark.layer_metrics import tick_index_share

read = tick_index_share.read
