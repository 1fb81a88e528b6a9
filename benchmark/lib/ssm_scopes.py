"""The named scopes of a tick with state-space layers (PR 56: `ssm` around
a Mamba-2 mixer, with `ssm_in` (the input projection), `ssm_conv` (the
causal convolution, its carried rows, and the split into u, B, C, delta),
`ssm_step` (the one-row segments' state update), `ssm_scan` (the longer
segments' chunked scan), `ssm_gate` (times silu(z), the norm) and `ssm_out`
(the output projection and the residual's sum) inside), made known to
`program_trace` as `hyper_scopes` makes a hyper-connected tick's known, and
for the same reason: `program_trace.SCOPES` is a literal in a file that
only a `benchmark` PR may edit. The driver of a cell whose model has such
layers calls `register()` when it is imported; cells of other drivers see
the set as it was. A `benchmark` PR should move the names into the literal
and delete this file.
"""
from __future__ import annotations

from . import program_trace

ALL = "ssm"
IN, CONV, STEP, SCAN, GATE, OUT = (
    "ssm_in", "ssm_conv", "ssm_step", "ssm_scan", "ssm_gate", "ssm_out")
SSM = (ALL, IN, CONV, STEP, SCAN, GATE, OUT)
# what of the mixer is not a matrix product with its weights
STATE = (CONV, STEP, SCAN)
ATTENTION = ("qkv", "paged_attention", "attn_out")


def register() -> None:
    program_trace.SCOPES = program_trace.SCOPES | set(SSM)
