"""The one-layer comparison that `correct` adds for a routed-expert model.

The token judge (agreement.py) cannot see part of an expert layer go
missing: in a float32 simulation of OLMoE's equations (PR 27's issue)
dropping each row's eighth expert still read 128 of 128 positions in
agreement. So the routed FFN of one layer is also compared directly:
`models.llama.routed_ffn` on seeded bf16 rows through layer 0's served
weights, against `reference_olmoe.expert_block` in float32 on the same
rows. Both route in float32 from the same input, so no expert flips but on
an exact tie, and the comparison can be tight.

A row agrees when the root mean square of its error is within TOL_ULPS
bf16 ulps (2^-8 each) of the root mean square of the whole reference
output. Measured on the chip at OLMoE's widths (my chip runs, PR 27, seven
seeds): the worst of 259 valid rows lay at 0.44-0.60 of this tolerance,
the worst of 16 at 0.30-0.46; bf16 rounds g, u, silu(g)*u and the down
projection's output, about 0.3 % of a row, and the worst row is one whose
output is larger than the mean. At two ulps one seed read 1.19, so two is
too tight for bf16 itself. What fails it
(benchmark/tests/test_moe_math.py, float32 on the CPU): expert weights
rounded to 8 bits (1.27 of the tolerance: the nearest fault, twice bf16's
reading), a dropped eighth expert, renormalised weights and a routed
padding row (each over 5).
"""
from __future__ import annotations

import numpy as np

TOL_ULPS = 4.0
BF16_ULP = 2.0 ** -8


def judge(out: np.ndarray, ref: np.ndarray):
    """out, ref [rows, d]. Returns (every row agrees, largest row error as
    a multiple of the tolerance)."""
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    tol = TOL_ULPS * BF16_ULP * np.sqrt(np.mean(ref * ref))
    err = np.sqrt(np.mean((out - ref) ** 2, axis=-1))
    worst = float(err.max() / tol) if tol > 0 else float("inf")
    return bool(np.isfinite(worst) and worst <= 1.0), worst
