"""The comparisons that `correct` adds for generation by diffusion over
blocks (SDAR), beside `agreement.judge` (every proposed token ties with the
reference's best) and `agreement_moe.judge` (one layer's routed FFN).

`judge_confidence`: the engine returns, for every masked row of every
denoise forward, its proposed token x0 and conf = softmax(logits)[x0]. The
token judge is a near-argmax test; this is the logit-level one: log conf
against the reference's log-probability of the same token at the same row,
within `agreement.TIE_TOL` (four bf16 ulps) of the row's largest |logit|,
in logit units, for `agreement.MIN_AGREEMENT` of the rows. log conf is
logit[x0] - logsumexp(logits): the first term carries the bf16 engine's
rounding of one logit (up to 1.2 x two ulps of the row maximum, PR 22), the
second averages 151,936 of them. Measured on the chip at the cell's widths
(PERF.md section 6, PR 32): the sound engine's worst row reads 0.36-0.61
of the tolerance (the token judge's largest gap 0.02-0.76), and the fault
nearest to both, the served path under the causal mask inside a block,
reads 4.90 with 48 % of the rows inside (the token judge: 72.6 % of the
rows tied, largest gap 7.19), same weights, same requests. A confidence
taken at the second-best token or a softmax at another temperature is off
by whole logit units (benchmark/tests/test_blockdiff.py).

`judge_transfer`: on the engine's OWN confidences the rows transferred are
the k most confident masked rows, ties to the lower position, exactly:
host arithmetic on what the device returned, no tolerance.

`judge_attention`: the block-causal read compared directly, because tokens
cannot see three keys of three hundred go missing (as PR 27 found for a
dropped expert): the mixed launch on seeded bf16 q, k, v against dense
float32 attention under the mask M. A row agrees when the root mean square
of its error is within ATTN_TOL_ULPS bf16 ulps (2^-8 each) of the root mean
square of the whole reference output. The launch rounds its output to
bf16 once (half an ulp of each element) and computes q.k exactly and p.v
in float32. The faults nearest to it, which must fail
(benchmark/tests/test_blockdiff.py, float32 on the CPU at the cell's
shapes): the causal mask inside a block (a block's first row loses its
three later keys) and a mask one block short (every row loses its own
block's four), each of 64 to 488 keys. Both readings are in PERF.md
section 6, PR 32.
"""
from __future__ import annotations

import numpy as np

from . import agreement, reference_sdar

ATTN_TOL_ULPS = 2.0
BF16_ULP = 2.0 ** -8


def record_forwards(eng) -> list:
    """Wrap `eng`'s block harvest so that every denoise sequence-forward
    from now on leaves one dict in the list returned: the request, the
    block's start, its ids and mask flags going in, every row's proposed
    token and confidence, the rows transferred, the request's steps. The
    engine itself records nothing; what `correct` judges is read here,
    from what the device returned and the sequence's state before the
    harvest changes it."""
    records, harvest = [], eng._harvest_blocks

    def recording(batch, in_block, out):
        for i, (seq, _n) in enumerate(batch.items):
            if in_block[i] and any(seq.block_masked):
                records.append(dict(
                    rid=seq.rid, start=seq.num_computed,
                    ids=list(seq.block_ids), masked=list(seq.block_masked),
                    proposed=out[i, :, 0].tolist(),
                    conf=out[i, :, 1].view(np.float32).tolist(),
                    taken=out[i, :, 2].astype(bool).tolist(),
                    steps=seq.denoising_steps))
        return harvest(batch, in_block, out)

    eng._harvest_blocks = recording
    return records


def judge_confidence(ref_logits: np.ndarray, proposed: np.ndarray,
                     conf: np.ndarray):
    """ref_logits [n, vocab] float32 at n masked rows, the engine's
    proposed tokens [n] and confidences [n]. Returns (rows within the
    tolerance, largest difference as a multiple of it)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    top = ref_logits.max(axis=-1)
    logz = top + np.log(np.exp(ref_logits - top[:, None]).sum(axis=-1))
    ref = ref_logits[np.arange(len(proposed)), proposed] - logz
    tol = agreement.TIE_TOL * np.abs(ref_logits).max(axis=-1)
    over = np.abs(np.log(np.asarray(conf, np.float64)) - ref) / tol
    return int((over <= 1.0).sum()), float(over.max())


def judge_transfer(masked, conf, taken, steps: int) -> bool:
    """One denoise forward: `taken` is exactly the min(masks left,
    ceil(Bd / steps)) masked rows of highest `conf`, ties to the lower
    position."""
    return bool(np.array_equal(reference_sdar.transfer(masked, conf, steps),
                               np.asarray(taken, bool)))


def judge_attention(out: np.ndarray, ref: np.ndarray):
    """out, ref [rows, ...]. Returns (every row agrees, largest row error
    as a multiple of the tolerance)."""
    out = np.asarray(out, np.float32).reshape(len(out), -1)
    ref = np.asarray(ref, np.float32).reshape(len(ref), -1)
    tol = ATTN_TOL_ULPS * BF16_ULP * np.sqrt(np.mean(ref * ref))
    err = np.sqrt(np.mean((out - ref) ** 2, axis=-1))
    worst = float(err.max() / tol) if tol > 0 else float("inf")
    return bool(np.isfinite(worst) and worst <= 1.0), worst
