"""The limits of the comparison that decides `correct` in the cell whose
model keeps a recurrent state a sequence in 36 Mamba-2 layers beside the
pages of 4 attention layers (granite-4.0-h-micro,
`serve_ssm_chat_decode64`). Each lies between two readings on the chip at
the published widths (my chip runs, PR 56; PERF.md section 6): what the
sound program reads over its seeds, and what is read when the recurrent
state is kept in bfloat16, the nearest precision below the configuration's
float32 (`engine.state_dtype`), which has to come out as not correct by one
of these limits, and does by STATE_TOL.

MIN_AGREEMENT: the share of judged positions (three requests x 64 generated
tokens, teacher-forced; and, judged apart at the same limit, 64 requests
live together x 16 tokens) at which the engine's token ties with the float32
reference's best logit (`agreement.judge`, four bf16 ulps of the row's
largest logit, unchanged). A share and not every position for
`agreement_latent`'s reasons: a bf16 stream against a float32 reference,
seeded weights whose best two logits often lie closer than bf16 tells
apart. The sound program read 0.9896 to 1.0 over 15 runs on 15 seeds (190
to 192 of 192; largest gap 0.67 to 1.47 of the tolerance; a position is
0.52 points). With the embedding drawn at 0.02 the tied head echoed its
input and the share read 1.0 with a largest gap of 0.0 whatever the layers
did (first run), which is why it is drawn at 0.02 / 12. A program that does
not read its carried state leaves the reference within a few tokens
(tests/test_granite4_ssm.py, CPU). The limit stands six positions under the
lowest sound reading. The 64 short requests (prompts of 24 to 87 ids, every
slot of the pool live in one tick, several prompts in one chunk's tick)
read 0.9932 to 0.9990 of 1,024 positions over 10 runs on 10 seeds (second
session; largest gap 1.03 to 1.51), the long ones 0.9948 to 1.0 there.

ROWS_TOL_ULPS: a row of the convolution's output and of y agrees when the
root mean square of its error is within this many bf16 ulps (2^-8) of the
root mean square of the reference's row. The convolution is rounded to bf16
once: 0.456 to 0.466 on every seed and shape. y of a one-row segment: 0.44
to 0.46 through the launch (the state rounded to bf16 in its read), 0.0 in
the stock form; of a longer segment 0.43 to 0.46 (the scan's products at
the matrix unit's default precision). A structural fault (a dropped tap, a
segment reading its neighbour, a block's decay off by a row) errs by whole
values.

OWN_CONV_TOL_ULPS: the same measure on y against the recurrence behind the
REFERENCE's own convolution in float32, so that nothing on that side was
prepared by the program. The program's side carries the convolution's one
rounding to bf16 through a sum of 128 products and y's own rounding: sound
0.67 to 0.80 of a bf16 ulp a row on every shape over 11 runs (0.34 to 0.40
of the limit; CPU, stock form, y in float32: 0.51 to 0.61). A bfloat16 pool
reads the same (0.65 to 0.72): this is a limit on the structure, twice the
rounding's size and far under a whole value; the limit that fails a lower
precision is STATE_TOL.

ATTN_TOL_ULPS: the same measure on the attention op's output rows, each
against its OWN size: contexts from 64 to 1,663 keys under a softmax scale
of 1/64 give rows that differ fourfold in size, and `agreement_blockdiff`'s
measure against the whole output's size read 0.74 to 0.78 (decode) and 1.11
to 1.23 (mixed) of ITS limit on the sound program. Sound: 0.615 to 0.660
over 8 runs. Sixteen keys of a thousand missing move a row by 1.6 %, four
ulps.

STATE_TOL: the largest relative error (root mean square over a slot's H x
P x N values) of a tick's new states against the recurrence in float32 ON
THE HOST'S CPU, from the rows the program's own convolution gave. Sound:
1.4e-6 to 4.7e-6 on every shape over 19 runs (0.014 to 0.047 of the limit);
0.0 on one-row segments in the stock form. Against the recurrence run ON
THE CHIP the same program read 1.3e-4, 1.7e-4 and 4.5e-4 to 5.7e-4 at
segments of 112, 150 and 449 rows, growing with the rows and unmoved by
two repairs of the scan's own sums: the chip's float32 exp, taken once a
token by the recurrence and once a block by the scan, is off by about 1e-6
one way. A state pool kept in bfloat16 reads 1.70e-3 to 1.77e-3 on the chip on all
four shapes (two seeds; y stays at 0.44, so no other limit sees it; CPU:
1.7e-3, tests/test_granite4_ssm.py): the limit is 20 times the sound program's
largest and a twentieth of that.

CARRIED_TOL: the same measure on the state a sequence holds in its slot
after a prefill of 1,136 rows in three chunks and 511 decode rows, the
largest of the 36 layers, against the reference's state behind the same
1,647 tokens. This is the bf16 STREAM's drift through 40 layers, not the
pool's precision: sound 0.070 to 0.075 over 15 runs. It is a limit on the
structure (a chunk's state lost at an edge, a slot mixed with another, a
state not zeroed at admission: uncorrelated states read 1.4), three times
over the sound reading.
"""
from __future__ import annotations

import numpy as np

MIN_AGREEMENT = 0.96
ROWS_TOL_ULPS = 1.0
OWN_CONV_TOL_ULPS = 2.0
ATTN_TOL_ULPS = 1.0
STATE_TOL = 1e-4
CARRIED_TOL = 0.25
BF16_ULP = 2.0 ** -8


def judge_rows(out: np.ndarray, ref: np.ndarray, ulps: float = 0.0):
    """out, ref [rows, ...]. A row agrees when the root mean square of its
    error is within `ulps` (0: ROWS_TOL_ULPS) bf16 ulps of the root mean
    square of the reference's row. Returns (every row agrees, the largest
    row error as a multiple of the tolerance)."""
    out = np.asarray(out, np.float32).reshape(len(out), -1)
    ref = np.asarray(ref, np.float32).reshape(len(ref), -1)
    tol = (ulps or ROWS_TOL_ULPS) * BF16_ULP * np.sqrt(
        np.mean(ref * ref, axis=-1))
    err = np.sqrt(np.mean((out - ref) ** 2, axis=-1))
    worst = float((err / np.maximum(tol, 1e-30)).max())
    return bool(np.isfinite(worst) and worst <= 1.0), worst


def state_error(out: np.ndarray, ref: np.ndarray) -> float:
    """The largest, over the leading axis (slots, or layers), of the root
    mean square of the error over the root mean square of the
    reference."""
    out = np.asarray(out, np.float64).reshape(len(out), -1)
    ref = np.asarray(ref, np.float64).reshape(len(ref), -1)
    err = np.sqrt(np.mean((out - ref) ** 2, axis=-1))
    return float((err / np.maximum(np.sqrt(np.mean(ref * ref, axis=-1)),
                                   1e-30)).max())


def judge_states(out: np.ndarray, ref: np.ndarray, tol: float):
    """Returns (every state within `tol`, the largest error as a multiple
    of it)."""
    worst = state_error(out, ref) / tol
    return bool(np.isfinite(worst) and worst <= 1.0), worst
