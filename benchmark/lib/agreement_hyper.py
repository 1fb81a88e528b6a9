"""The limits of the comparison that decides `correct` in the cell whose
model mixes a residual stream of four lanes by manifold-constrained
hyper-connections around latent attention and 64 whole-held experts
(Xing4.0-29B-A4B, `serve_hyper_latent_mixed_4k`). Each lies between two
readings on the chip at the published widths (my chip runs, PR 54; PERF.md
section 6): what the sound program reads over its seeds, and what the
float32 reference reads against itself when its weights are rounded to 8
bits (3 bits of mantissa at bf16's range: e4m3 under an ideal scale), the
nearest precision below the stated bf16, or, for the coefficients, when
they are made in bfloat16.

MIN_AGREEMENT: the share of judged positions (three requests x 64
generated tokens, teacher-forced) at which the engine's token ties with
the float32 reference's best logit (`agreement.judge`, four bf16 ulps of
the row's largest logit, unchanged). A share and not every position for
`agreement_latent`'s reasons (bf16 absorbed queries, probabilities and
stream against a float32 reference; seeded weights whose best two logits
often lie closer than bf16 tells apart), and lower than that cell's 0.96
because here EVERY routed expert is held: a row whose fourth and fifth
router scores bf16 cannot tell apart takes another expert than the
reference, and that expert carries 2 / 4 of the layer's routed output
(Kimi's chip computes 12 of 384, so a flip there seldom moves a pair it
holds; Laguna's cell, every expert held too, stands at 0.90 for the same
reason). The reference itself with nothing but its lanes rounded to bf16
leaves the float32 reference's best token at 1.6 and 3.1 % of the
positions (two seeds). The sound program read 0.885 to 0.974 over 40 runs
on 40 seeds (mean 0.929, standard deviation 0.019; a position is 0.52
points); the reference on 8-bit weights 0.41, 0.54 and 0.60. The limit is
5.8 standard deviations under the sound mean, 0.065 under its lowest and
0.22 over the highest of the lowered.

COEFF_TOL: the largest absolute error of any of a row's 24 mixing
coefficients (4 sigmoids in (0, 1), 4 in (0, 2), 16 entries of a doubly
stochastic matrix in (0, 1)) against `reference_xing4.coefficients` in
float32, on seeded bf16 rows at a decode tick's and a chunk tick's row
counts through the served phi, b and alpha. The program makes them in
float32 from bf16 operands (exact products, float32 sums), so its error is
that of float32 sums in another order and of the chip's exp and divide:
3.6e-7 to 1.9e-6 over 26 seeds (0.007 to 0.037 of the limit). Coefficients
made in bfloat16 read 5.1e-3 (102 times the limit), on 8-bit weights
2.6e-2 to 3.4e-2 (528 to 675).

ROWS_TOL_ULPS: a row of the sub-block's input and of the updated stream
agrees when the root mean square of its error is within this many bf16
ulps (2^-8) of the root mean square of the reference's row. The program
rounds each to bf16 once: 0.43 to 0.45 on every seed. This limit is for
the mix's STRUCTURE (a transposed matrix, a dropped lane, coefficients in
the wrong order err by whole values; 8-bit weights read 3.2 to 6.3):
bfloat16 coefficients read 0.75 to 0.97 and pass it, and are
`COEFF_TOL`'s to catch.

One sparse layer's routed FFN is judged by `agreement_moe` unchanged
(four ulps): the sound program reads 0.26 to 0.30 of it, 8-bit weights 39
to 44.
"""
from __future__ import annotations

import numpy as np

MIN_AGREEMENT = 0.82
COEFF_TOL = 5e-5
ROWS_TOL_ULPS = 1.0
BF16_ULP = 2.0 ** -8


def judge_coefficients(out: np.ndarray, ref: np.ndarray):
    """out, ref [rows, n^2 + 2n]. Returns (all within COEFF_TOL, the
    largest error as a multiple of it)."""
    worst = float(np.abs(np.asarray(out, np.float64)
                         - np.asarray(ref, np.float64)).max() / COEFF_TOL)
    return bool(np.isfinite(worst) and worst <= 1.0), worst


def judge_rows(out: np.ndarray, ref: np.ndarray):
    """out, ref [rows, width]. Returns (every row agrees, the largest row
    error as a multiple of the tolerance)."""
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    tol = ROWS_TOL_ULPS * BF16_ULP * np.sqrt(np.mean(ref * ref, axis=-1))
    err = np.sqrt(np.mean((out - ref) ** 2, axis=-1))
    worst = float((err / tol).max())
    return bool(np.isfinite(worst) and worst <= 1.0), worst
