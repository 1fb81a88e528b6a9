"""The limits the cell `serve_sparse_gqa_sessions_longctx` holds its program
to beside the accepted ones (Keye-VL-2.0-30B-A3B: heads' own keys and
values under a learned sparse index, 16 of 128 routed experts held). Each
from two readings on the chip at the published widths (my chip runs, PR 50;
PERF.md section 6): the largest the sound program gave over its seeds, and
what a variant one precision lower gave, which has to fail.

`MIN_AGREEMENT`: the share of judged positions that must tie with the
reference's best (`agreement.judge`, its tolerance of four bf16 ulps
unchanged): 384 positions (six requests x 64 generated tokens,
teacher-forced: under `topk`, across it, far above it, one past the
crossing of the two sparse reads whose decode rows gather, a session's next
turn over cached pages, a prompt that copies a cached page). The sound
program read 1.0 on the first five requests' 320 positions in fourteen runs
of fifteen and 0.9969 (319) in one; with the sixth, 1.0 (384 of 384) in
four runs of nine, 0.9974 (383) in four and 0.9922 (381) in one, whose long
request alone tied at 61 of 64 (with every one-row sequence walking, the
same seed reads 62 of 64: the 41k context's misses, not the gather's), the
largest gap over the tolerance of a tying position 2.39. It reads higher than dots3-note's
0.906-0.974 because nothing rescales the keys here: a key exchanged at the
selection's edge moves a row by less than rounding does. The limit leaves
eleven positions of 384. What the tokens read with index keys or pages in 8
bits IN THE ENGINE'S PATH has not been measured (as in the sibling cell);
lowered precision is caught where it is made, by the two limits below and
the pools' bits.

`MIN_SELECTION`: the share of rows whose selected SET (`paged_index_select`
at a timed tick's shapes, on seeded bf16 inputs) must equal the reference's
stable full sort of float32 scores of the SAME inputs. Sound: 1.0 on every
one of 313 rows in each of seventeen runs (products of bf16 values are exact
in float32 and only the order of 64 additions differs). With the index keys
rounded to 8 bits (float8 e4m3) on their way into their pages: 0.0, and the
index pool no longer holds the keys bit for bit. Keys and values in 8 bits
leave the sets alone and fail the pools' bits and the read's accepted
tolerance (`agreement_blockdiff.judge_attention`: 5.7-6.0 of it, where the
sound read gives 0.32-0.35).

`MAX_FFN_ERROR`: the largest row error of one layer's routed FFN with the
held experts, in units of `agreement_moe.judge`'s own tolerance (four bf16
ulps of the root mean square of the whole reference output). That
tolerance was read on layers where every row sums eight experts (OLMoE:
0.44-0.60 of it) or a shared expert beside its held ones (Kimi, dots3).
Here a row sums only the experts HELD here of its eight, none to four of
them and no shared one, so a third of a chunk tick's rows are exactly zero
(which lowers the root mean square the tolerance is taken from) and a row
with three or four held experts is three to four times that size, rounded
in bf16 like any other: the sound program read 0.85-1.14 at a chunk tick's
2,047 rows over sixteen runs (0.41-0.56 at a decode tick's 16), i.e. the
accepted limit of 1 lies INSIDE bf16's own readings for this layer. With
the held experts' weights in 8 bits (float8 e4m3) the same rows read 16.3
(7.97 at 16 rows). The limit lies between, with room on both sides: 1.75
times the largest sound reading, a quarter of the smallest 8-bit one.
"""
from __future__ import annotations

MIN_AGREEMENT = 0.97
MIN_SELECTION = 0.98
MAX_FFN_ERROR = 2.0
