"""The comparison that decides `correct` for a serving cell.

The engine returns tokens, not logits, so agreement is judged teacher-
forced: the reference is fed the prompt and the tokens the engine chose,
and at every generated position the engine's token must be the
reference's best token or tie with it. With random weights the best two
logits often lie closer than bf16 can tell apart, so exact token equality
would fail on rounding alone; a wrong cache page, a wrong position or a
dropped chunk moves the logits by whole units and fails.

The arithmetic follows chip_smoke.py's `agreement` (PR 22); the original
stays there for the start-up proof.
"""
from __future__ import annotations

import numpy as np

# A token ties with the best when the reference puts it within four bf16
# ulps (2^-7 each, relative to the row's largest |logit|) of it. On the
# chip the bf16 engine sat up to 1.2 x two ulps from the float32
# reference (PR 22: at two ulps 1 of 94 tokens failed at 1.16 of the
# tolerance; at four the largest gap was 0.58). Computing in a precision
# below bf16 (8-bit weights or pages: ulp 2^-3..2^-4 of the row maximum)
# fails it.
TIE_TOL = 4 * 2.0 ** -7
# The share of judged positions that must tie or match. At depth 16 on
# the chip 1 of 576 positions lay outside the tolerance (at 1.20 of it;
# my chip runs, PR 24), so of a run's 288 positions 0.5 are expected to
# and 6 (0.98) practically never are, while a wrong page, position or
# chunk fails nearly every position.
MIN_AGREEMENT = 0.98


def judge(ref_logits: np.ndarray, chosen: np.ndarray):
    """ref_logits [n, vocab] float32 at the n generated positions, chosen
    [n] the engine's tokens. Returns (share in agreement, largest gap as a
    multiple of the tolerance)."""
    ref_logits = np.asarray(ref_logits, np.float32)
    chosen = np.asarray(chosen)
    tol = TIE_TOL * np.abs(ref_logits).max(axis=-1)
    gaps = ref_logits.max(axis=-1) - ref_logits[np.arange(len(chosen)), chosen]
    ok = gaps <= tol
    return float(ok.mean()), float((gaps / tol).max())
