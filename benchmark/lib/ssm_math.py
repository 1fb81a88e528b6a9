"""Bytes and operations that the recurrent state of state-space layers
(Mamba-2) needs in a serving tick, from counts the engine reports and the
configuration's shapes alone, never from what an implementation moved or
multiplied. Kept with the benchmark so that no PR which claims a gain can
change what a roofline share is measured against. Conventions as in
model_math: one multiply-add is 2 FLOPs.

The engine counts, a tick and summed over the state-space layers:
`ssm_step_rows`, the segments of ONE row (a decode row: its sequence's
state is read, updated by one rank-one term and written); `ssm_scan_rows`
and `ssm_segments`, the rows and the count of the segments of MORE than one
row (a prefill chunk: its sequence's state is read once and written once,
whatever the rows between).

A state is H x P x N values in the configuration's `state_dtype` (float32:
2,097,152 B at granite-4.0-h-micro's 64 x 64 x 128) beside the
convolution's K - 1 carried rows of conv_dim bf16 values (26,112 B). A
one-row update reads and writes both: 2 x 2,123,264 = 4,246,528 B a (row,
layer), and nothing else worth counting (its u, B, C, delta and y are 17
KB). A longer segment pays the same once, and for each row its u, B, C
(bf16), delta (float32) and y (bf16) once; in FLOPs, whatever the block
length of the chunked form, every row builds its term of the carried state
(2 H P N) and reads the state it is handed (2 H P N): 4 H P N a (row,
layer). The in-block term (rows x block x H P) is left out so that the
floor holds at any block length: the share may read low, never over 100 %.
"""
from __future__ import annotations

from typing import Optional

from . import program_trace, ssm_scopes
from .model_math import least_seconds

STATE_BYTES = {"float32": 4, "bfloat16": 2}


def shapes(cfg: dict):
    """(H, P, N, G, K, conv_dim) of a configuration file."""
    H, P, N, G, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                     cfg["mamba_d_state"], cfg["mamba_n_groups"],
                     cfg["mamba_d_conv"])
    return H, P, N, G, K, H * P + 2 * G * N


def ssm_layers(cfg: dict) -> int:
    return sum(t == "mamba" for t in cfg["layer_types"])


def slot_bytes(cfg: dict) -> int:
    """One sequence's recurrent state in one layer: the state in the
    engine's `state_dtype` and the convolution's carried rows in bf16."""
    H, P, N, _, K, conv = shapes(cfg)
    return (H * P * N * STATE_BYTES[cfg["engine"]["state_dtype"]]
            + (K - 1) * conv * 2)


def step_bytes(cfg: dict) -> int:
    """A one-row segment in one layer: its slot read and written."""
    return 2 * slot_bytes(cfg)


def scan_row_bytes(cfg: dict) -> int:
    """One row of a longer segment in one layer: u, B, C in bf16, delta in
    float32, y in bf16, each once."""
    H, P, N, G, _, _ = shapes(cfg)
    return 2 * (H * P + 2 * G * N) + 4 * H + 2 * H * P


def scan_row_flops(cfg: dict) -> int:
    H, P, N, _, _, _ = shapes(cfg)
    return 4 * H * P * N


def step_least_seconds(cfg: dict, step_rows: int, peaks: dict) -> float:
    return step_rows * step_bytes(cfg) / peaks["hbm_bytes_per_s"]


def scan_least_seconds(cfg: dict, rows: int, segments: int, peaks: dict):
    """Roofline floor of one tick's longer segments (`rows`, `segments`
    summed over the layers by the engine). Returns (seconds, "compute" |
    "memory")."""
    return least_seconds(
        float(rows * scan_row_flops(cfg)),
        float(segments * step_bytes(cfg) + rows * scan_row_bytes(cfg)),
        peaks)


def _ticks(record, *needs):
    trace = program_trace.of_record(record)
    if trace is None or record.trace is None:
        return None
    return [e[3] for e in trace["program_spans"]
            if e[0] == program_trace.STEP and all(n in e[3] for n in needs)]


def state_update_roofline(record) -> Optional[float]:
    """100 x the traced ticks' one-row updates' least time over the self
    time they took: all of `ssm_step` (it runs for the one-row segments
    alone, in every tick) and of `ssm_conv` the one-row segments' share BY
    ROWS (the convolution runs over a tick's whole stream: a chunk's rows
    are charged to the scan). None without a trace, the fields or the
    scopes."""
    ticks = _ticks(record, "ssm_step_rows", "ssm_scan_rows")
    step = program_trace.scope_share(record, ssm_scopes.STEP)
    conv = program_trace.scope_share(record, ssm_scopes.CONV)
    if not ticks or not step:
        return None
    ctx = record.context
    rows = sum(f["ssm_step_rows"] for f in ticks)
    other = sum(f["ssm_scan_rows"] for f in ticks)
    if not rows:
        return None
    took = (step + (conv or 0.0) * rows / (rows + other)
            ) / 100.0 * record.trace["busy_s"]
    return 100.0 * step_least_seconds(ctx.config, rows, ctx.peaks) / took


def chunk_scan_roofline(record) -> Optional[float]:
    """100 x the traced ticks' longer segments' least time (the floor
    taken a tick) over the self time under `ssm_scan`."""
    ticks = _ticks(record, "ssm_scan_rows", "ssm_segments")
    took = program_trace.scope_share(record, ssm_scopes.SCAN)
    if not ticks or not took:
        return None
    ctx = record.context
    floor = sum(scan_least_seconds(ctx.config, f["ssm_scan_rows"],
                                   f["ssm_segments"], ctx.peaks)[0]
                for f in ticks)
    return 100.0 * floor / (took / 100.0 * record.trace["busy_s"])


def slots_live_share(record) -> Optional[float]:
    """Percent of the state slots that held a sequence, a traced tick's
    mean."""
    ticks = _ticks(record, "state_slots_live")
    if not ticks:
        return None
    slots = record.context.config["engine"]["state_slots"]
    return 100.0 * sum(f["state_slots_live"] for f in ticks) / (
        len(ticks) * slots)
