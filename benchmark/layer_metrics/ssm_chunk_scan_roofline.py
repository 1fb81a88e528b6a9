"""The least time the chip could take for the traced ticks' segments of
more than one row through the state-space layers (a tick: the larger of a
segment's state read and written once with each row's u, B, C, delta and y
once over the HBM bandwidth, and 4 N H P FLOPs a row over the bf16 peak;
the engine's `ssm_scan_rows` and `ssm_segments`), over the self time under
`ssm_scan` (benchmark/lib/ssm_math.py). The in-block term is left out of
the floor, so the share holds at any block length and may read low."""
from benchmark.lib import ssm_math


def read(record):
    return ssm_math.chunk_scan_roofline(record)
