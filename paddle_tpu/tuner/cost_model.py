"""Analytic step-time cost model: compose measured op costs, simulated
pipeline bubbles and a link-bandwidth comm estimate into a predicted
step time per candidate config.

The model owns NO timing heuristics of its own — every term is one of
the three ingredients the repo already measures (the "Operator Fusion in
XLA" argument: measured per-op costs beat hand-tuned heuristics):

- **op costs** come from ``tools/op_bench_baseline.json`` (the
  ``ci_op_benchmark`` pin for this machine class) or a fresh in-process
  ``measure(only=...)`` when an entry is missing/stale
  (:meth:`OpCosts.refresh`);
- **pipeline bubble** comes from ``schedule.simulate()`` — the EXACT
  dependency-timed makespan of the candidate's validated action lists,
  never the closed form (so zbh1's BW bubble-fill and interleave's
  group contention are priced correctly);
- **comm cost** is wire bytes (the same accounting the
  ``paddle_dp/pp_wire_bytes_total`` counters use: dtype ratio + the
  int8 codec's per-block scale overhead) divided by a measured
  bytes/sec link estimate, plus the measured per-bucket pack/decode
  executable cost.

Training candidates are ranked by predicted step seconds; serving
candidates by predicted seconds per decode token (the inverse of
tokens/s), so one ``cost`` scalar orders any space.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, Optional

from ..core import flags
from ..distributed.pipeline import schedule as _sched
from ..observability import emit as _emit

__all__ = ["OpCosts", "Workload", "CostModel", "entry_time", "entry_noise",
           "estimate_link_bytes_per_s", "machine_key", "BASELINE_PATH"]

BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tools", "op_bench_baseline.json")


def entry_time(entry) -> Optional[float]:
    """Seconds from a baseline entry: legacy bare float or the
    dispersion-carrying ``{"t": median, "noise": rel}`` dict (PR 19's
    noisy-CPU fix). ``None`` for error entries."""
    if isinstance(entry, (int, float)):
        return float(entry)
    if isinstance(entry, dict) and isinstance(entry.get("t"), (int, float)):
        return float(entry["t"])
    return None


def entry_noise(entry) -> float:
    """Relative measurement dispersion (IQR/median) of a baseline entry;
    0.0 for legacy bare-float pins (no recorded dispersion)."""
    if isinstance(entry, dict) and isinstance(entry.get("noise"),
                                              (int, float)):
        return max(0.0, float(entry["noise"]))
    return 0.0


def machine_key(platform: Optional[str] = None) -> str:
    """The op-bench baseline key for this process: platform + cpu count
    (kept in lockstep with tools/ci_op_benchmark.py)."""
    if platform is None:
        import jax

        platform = jax.devices()[0].platform
    try:
        ncpu = len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        ncpu = os.cpu_count()
    return f"{platform}/{ncpu}cpu"


class OpCosts:
    """Per-op timings for one machine class, loaded from the pinned
    baseline and optionally refreshed in-process for missing entries."""

    def __init__(self, path: Optional[str] = None,
                 key: Optional[str] = None):
        self.path = path or BASELINE_PATH
        self.key = key or machine_key()
        self.times: Dict[str, float] = {}
        self.noises: Dict[str, float] = {}
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        # a machine class without pins has no timings at all: a lookup
        # that refresh() has not filled then fails, where a default would
        # price the candidate from nothing (or from another machine)
        self.pinned = self.key in data
        for name, entry in (data.get(self.key) or {}).items():
            t = entry_time(entry)
            if t is not None:
                self.times[name] = t
                self.noises[name] = entry_noise(entry)

    def time(self, name: str, default: Optional[float] = None
             ) -> Optional[float]:
        if name not in self.times and not self.pinned:
            raise KeyError(
                f"no timing of {name!r} for machine {self.key!r}: "
                f"{self.path} holds no pins for it (run "
                f"tools/ci_op_benchmark.py --update there) and refresh() "
                f"has not measured it")
        return self.times.get(name, default)

    def noise(self, name: str) -> float:
        return self.noises.get(name, 0.0)

    def refresh(self, names: Iterable[str], reps: int = 10) -> None:
        """Fresh in-process measurement of ``names`` (via the op-bench
        basket), overriding the pinned values — the offline tuner calls
        this so a stale pin can't steer the search."""
        names = [n for n in names]
        if not names:
            return
        import importlib.util

        bench_py = os.path.join(os.path.dirname(self.path),
                                "ci_op_benchmark.py")
        spec = importlib.util.spec_from_file_location("_ci_op_bench",
                                                      bench_py)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for name, entry in mod.measure(reps=reps, only=set(names),
                                       detail=True).items():
            t = entry_time(entry)
            if t is not None:
                self.times[name] = t
                self.noises[name] = entry_noise(entry)


def estimate_link_bytes_per_s(size_mb: int = 8, rounds: int = 3) -> float:
    """Measured bytes/sec for moving one buffer onto the accelerator —
    the link estimate that scales wire bytes into comm seconds.
    ``FLAGS_tune_link_bytes_per_s > 0`` pins it instead (multi-host ICI
    vs the single-host device_put proxy measured here)."""
    pinned = float(flags.flag_value("tune_link_bytes_per_s"))
    if pinned > 0:
        return pinned
    import jax
    import numpy as np

    buf = np.zeros(size_mb << 20, dtype=np.uint8)
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.device_put(buf).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return len(buf) / max(best, 1e-9)


# int8 codec wire overhead: one float32 absmax scale per block of
# ``block`` elements (distributed/quant_comm.py's layout)
def _wire_ratio(comm_dtype: str, block: int) -> float:
    d = (comm_dtype or "").lower()
    if d in ("bf16", "bfloat16", "fp16", "float16"):
        return 0.5
    if d == "int8":
        return (1.0 + 4.0 / max(1, block)) / 4.0
    return 1.0


@dataclass
class Workload:
    """One pinned (model, topology) the tuner optimizes for.

    ``stage_phase_s`` is the measured cost of ONE schedule action (one
    microbatch forward OR backward on one stage) — the unit cost
    ``schedule.simulate()``'s makespan is denominated in. The serving
    fields name the op-bench tick entries whose geometry anchors the
    decode-tick composition.
    """
    name: str
    kind: str = "train"              # "train" | "serving"
    pp: int = 1
    dp: int = 1
    n_layers: int = 2
    grad_bytes: int = 0              # fp32 gradient bytes per replica/step
    param_bytes: int = 0             # fp32 param bytes (ZeRO-1 all-gather)
    stage_phase_s: float = 1.0
    # serving anchors: the op-bench micro-entries' measured geometry
    tick_layers: int = 2
    tick_batch: int = 4              # slots in the block_mha_decode entry
    tick_budget: int = 64            # token budget of the tick entries
    ffn_rows: int = 128              # rows in the ffn_fwd entries
    extra: dict = field(default_factory=dict)


class CostModel:
    """Predict step time for a :class:`~paddle_tpu.tuner.search.Candidate`
    against a :class:`Workload`."""

    def __init__(self, costs: Optional[OpCosts] = None,
                 link_bytes_per_s: Optional[float] = None):
        self.costs = costs or OpCosts()
        self._link = link_bytes_per_s

    @property
    def link_bytes_per_s(self) -> float:
        if self._link is None:
            self._link = estimate_link_bytes_per_s()
        return self._link

    # -- pipeline bubble: simulate(), never the closed form ---------------
    def bubble(self, pp_schedule: str, pp: int, microbatches: int,
               virtual: int = 1) -> dict:
        """Exact simulated bubble for a candidate schedule: returns
        ``{"bubble_fraction", "makespan", "actions"}`` where makespan is
        in schedule-action units (1 unit = one F or B of one microbatch
        on one stage) and bubble_fraction is bit-identical to
        ``schedule.simulate()`` on the same validated lists."""
        sched = _sched.normalize(pp_schedule)
        P = pp * max(1, virtual)
        acts = _sched.build_schedule(sched, P, microbatches)
        sim = _sched.simulate(acts, P, groups=pp)
        actions = sum(len(v) for v in acts.values())
        return {"bubble_fraction": sim["bubble_fraction"],
                "makespan": sim["makespan"], "actions": actions}

    # -- term builders ----------------------------------------------------
    def _train_terms(self, w: Workload, c) -> dict:
        bub = self.bubble(c.pp_schedule, w.pp, c.pp_microbatches,
                          c.pp_virtual_degree)
        compute_s = bub["makespan"] * w.stage_phase_s
        # dp gradient sync: wire bytes at the candidate dtype's ratio
        # over the measured link, ring-allreduce volume 2(N-1)/N
        ratio = _wire_ratio(c.dp_comm_dtype, c.dp_comm_block)
        comm_s = pack_s = gather_s = 0.0
        if w.dp > 1 and w.grad_bytes:
            wire = w.grad_bytes * ratio
            comm_s = wire * 2.0 * (w.dp - 1) / w.dp / self.link_bytes_per_s
            if c.dp_shard_update and w.param_bytes:
                # ZeRO-1 all-gathers params back after the sharded step
                gather_s = (w.param_bytes * (w.dp - 1) / w.dp
                            / self.link_bytes_per_s)
        if w.grad_bytes:
            d = (c.dp_comm_dtype or "").lower()
            if d == "int8":
                per_bucket = ((self.costs.time("dp_q8_pack_cached") or 0.0)
                              + (self.costs.time("dp_q8_decode_cached")
                                 or 0.0))
            elif d in ("bf16", "bfloat16", "fp16", "float16"):
                per_bucket = self.costs.time("dp_flat_pack_bf16_cached",
                                             0.0) or 0.0
            else:
                per_bucket = self.costs.time("dp_flat_pack_cached",
                                             0.0) or 0.0
            n_buckets = max(1, -(-w.grad_bytes
                                 // max(1, c.dp_bucket_mb << 20)))
            pack_s = n_buckets * per_bucket
        step_s = compute_s + comm_s + pack_s + gather_s
        return {"cost": step_s, "step_s": step_s,
                "bubble_fraction": bub["bubble_fraction"],
                "makespan": bub["makespan"],
                "terms": {"compute_s": compute_s, "comm_s": comm_s,
                          "pack_s": pack_s, "gather_s": gather_s}}

    def _serving_terms(self, w: Workload, c) -> dict:
        """One decode tick composed from the tick/attention/FFN
        micro-entries: the measured stock tick plus the per-op delta of
        the one kernel lever a candidate holds (the fused FFN) — the
        fusion-paper discipline of predicting from the most aggregate
        measurement available. The attention read is no candidate's to
        choose (the engine follows ops/pallas/paged_attention.selected),
        so it is priced as the anchor tick ran it and is the same for
        every candidate of one machine."""
        t = self.costs.time
        base = t("decode_tick_stock")
        if base is None:
            raise ValueError(
                f"cost model needs a 'decode_tick_stock' entry under "
                f"{self.costs.key!r} in {self.costs.path} — run "
                f"tools/ci_op_benchmark.py --update (or .refresh())")
        attn_e = t("block_mha_decode_stock", 0.0)
        ffn_stock = t("ffn_fwd_stock", 0.0)
        ffn_e = t("ffn_fwd_pallas", ffn_stock) if c.pallas_ffn else ffn_stock
        L = w.tick_layers
        anchor = base + L * (ffn_e - ffn_stock)
        # scale the variable portion to the candidate geometry: the
        # attention launch walks batch-slot rows, the FFN walks the
        # padded token_budget rows (executables are keyed on both)
        attn_s = L * attn_e * (c.max_batch / max(1, w.tick_batch))
        ffn_s = L * ffn_e * (c.token_budget / max(1, w.ffn_rows))
        host_s = max(0.0, anchor - L * attn_e - L * ffn_e
                     * (w.tick_budget / max(1, w.ffn_rows)))
        # multi-tenant LoRA: the segmented apply is an S-slot-wide
        # gathered einsum riding the FFN-shaped row walk — compute grows
        # linearly in device slots (the pack is dense over slots, active
        # or not), while the LRU miss probability under uniform tenant
        # traffic falls as slots approach the tenant count, each miss
        # paying a measured host-side swap. Both extras default to 0, so
        # a workload that doesn't serve adapters prices every slot count
        # identically.
        slots = max(1, int(getattr(c, "adapter_slots", 1)))
        ad_ratio = float(w.extra.get("adapter_flop_ratio", 0.0))
        adapter_s = ffn_s * ad_ratio * slots
        tenants = int(w.extra.get("adapter_tenants", 0))
        swap_s = 0.0
        if tenants > slots:
            swap_s = (float(w.extra.get("adapter_swap_s", 0.0))
                      * (1.0 - slots / tenants))
        tick_s = host_s + attn_s + ffn_s + adapter_s + swap_s
        # speculative decoding: k draft steps (each draft_cost_ratio of a
        # target tick) buy 1 + acceptance*k emitted tokens per verify
        # tick. With no draft priced (draft_cost_ratio absent/0) the term
        # vanishes and spec_k is cost-neutral — the engine without a
        # draft attached never runs spec ticks.
        k = max(0, int(getattr(c, "spec_k", 0)))
        draft_ratio = float(w.extra.get("draft_cost_ratio", 0.0))
        spec_s = 0.0
        tokens_per_tick = 1.0
        if k and draft_ratio > 0.0:
            spec_s = tick_s * k * draft_ratio
            tokens_per_tick = 1.0 + float(
                w.extra.get("spec_acceptance", 0.0)) * k
        tick_total = tick_s + spec_s
        tok_s = c.max_batch * tokens_per_tick / max(tick_total, 1e-12)
        return {"cost": tick_total / (max(1, c.max_batch)
                                      * tokens_per_tick),
                "tick_s": tick_total, "tokens_per_s": tok_s,
                "anchor": "decode_tick_stock",
                "terms": {"host_s": host_s, "attn_s": attn_s,
                          "ffn_s": ffn_s, "adapter_s": adapter_s,
                          "swap_s": swap_s, "spec_s": spec_s,
                          "tokens_per_tick": tokens_per_tick}}

    def predict(self, w: Workload, c) -> dict:
        """Predicted cost dict for one candidate. ``cost`` is the
        ranking scalar: step seconds for training workloads, seconds
        per decode token for serving workloads (lower is better)."""
        out = (self._train_terms(w, c) if w.kind == "train"
               else self._serving_terms(w, c))
        _emit("tuner.predict", workload=w.name, cost=out["cost"])
        return out
