"""`tick_sparse_attention_share` of a tick whose layers attend over heads'
own keys and values (PR 50): the share of device busy time that is self
time under scope `paged_attention_sparse` (here the masked walks and the
gather of the selected positions' keys and values), by the accepted
reader; an entry of its own because that reader's cell list is the latent
cell's alone. None where the program writes no such scope."""
from benchmark.layer_metrics import tick_sparse_attention_share

read = tick_sparse_attention_share.read
