"""The least time the chip could take for the traced ticks' one-row state
updates (each decode row's slot read and written once a state-space layer:
4,246,528 B at granite-4.0-h-micro's widths, over the HBM bandwidth; the
count is the engine's `ssm_step_rows` on each step span, whatever
implements the update), over the self time under `ssm_step` and the
one-row segments' share by rows of `ssm_conv` (benchmark/lib/ssm_math.py)."""
from benchmark.lib import ssm_math


def read(record):
    return ssm_math.state_update_roofline(record)
