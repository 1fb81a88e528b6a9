"""The share of judged positions that must tie with the reference's best
(`agreement.judge`, its tolerance of four bf16 ulps unchanged) in the cell
whose model attends over latent pages and holds 12 of 384 routed experts
(Kimi-K2.6's share, `serve_latent_longctx_decode`).

What is judged, and why a share and not every position: 192 positions
(three requests x 64 generated tokens, teacher-forced), each the engine's
chosen token against the float32 reference's logits at that position. With
seeded weights a row's best two logits often lie closer than bf16 can tell
apart, and the served path rounds where the reference does not: the
absorbed query (q_nope Wkvb,K^T, 512 values a head) and the latent output
before Wkvb,V are bf16, the probabilities are rounded to bf16 for p.v, and
the residual stream is bf16. A row's router choice can flip as in Laguna's
cell, but only 12 of 384 experts are held, so a flip changes a held pair in
one (row, layer) of about thirty and the sigmoid scores' renormalised
weights 2.827 / 8 carry less of the stream than the shared expert does.

The limit lies between two readings on the chip at the published widths
(my chip runs, PR 41; PERF.md section 6): the sound program read 0.9792 to
1.0 over 24 seeds of the check alone (mean 0.9961, standard deviation
0.0055; a position is 0.52 points) and 0.9896 to 1.0 in the cell's own
runs, so the limit is 6.6 standard deviations under the mean; the served
path with its latent pages cut to 8 bits on their way into the pool read
0.9323 and 0.9479, with the scores' operands cut to 8 bits 0.7396. Each of
those is also caught where it is made, and by a wide margin there: the pool
no longer holds the rows bit for bit, and the attention op reads 5.3 and 25
to 28 times its tolerance (`agreement_blockdiff.judge_attention`); experts'
weights cut to 8 bits move no token that this check sees (0.25 held pairs
a row a layer) and read 4.0 and 5.6 times `agreement_moe.judge`'s
tolerance on a sparse layer.
"""
from __future__ import annotations

MIN_AGREEMENT = 0.96
