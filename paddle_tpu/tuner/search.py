"""Candidate space enumeration + analytic pruning + top-k ranking.

The search is deliberately dumb-but-exhaustive: the config space the
repo actually exposes (dp bucket size, grad-comm dtype + block size, pp
schedule x microbatches x virtual degree, ZeRO-1, the fused Pallas FFN,
serving token budget x max batch) is small enough — hundreds, not
millions — that full enumeration under the ANALYTIC model is cheap,
and only the survivors pay for real validation runs. Pruning is a
ratio bound: a candidate whose predicted cost exceeds
``FLAGS_tune_prune_ratio`` x the analytic incumbent is never measured
(the default 1.3 margin covers the cost model's own error — see
``tests/test_tuner.py::test_pruning_never_discards_measured_winner``
for the seeded-toy-space guarantee).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, fields, replace
from typing import Dict, Iterable, List, Optional, Sequence

from ..core import flags
from ..observability import emit as _emit
from .cost_model import CostModel, Workload

__all__ = ["Candidate", "Ranked", "enumerate_space", "search"]


@dataclass(frozen=True)
class Candidate:
    """One point in the tunable-flag space. Defaults are the repo's
    hand-picked defaults, so ``Candidate()`` IS the incumbent config."""
    # data-parallel gradient sync
    dp_bucket_mb: int = 25           # DataParallel(comm_buffer_size_MB=)
    dp_comm_dtype: str = ""          # FLAGS_dp_grad_comm_dtype
    dp_comm_block: int = 256         # FLAGS_dp_comm_block_size
    dp_shard_update: bool = False    # FLAGS_dp_shard_update (ZeRO-1)
    # pipeline
    pp_schedule: str = "1f1b"        # FLAGS_pp_schedule
    pp_microbatches: int = 1         # FLAGS_pp_accumulate_steps
    pp_virtual_degree: int = 1       # FLAGS_pp_virtual_degree
    # kernels
    pallas_ffn: bool = False         # FLAGS_pallas_ffn
    # serving step geometry
    token_budget: int = 64           # FLAGS_serving_token_budget
    max_batch: int = 8               # FLAGS_serving_max_batch
    # multi-tenant serving: speculative depth + adapter device slots
    spec_k: int = 4                  # FLAGS_spec_k (draft tokens/tick)
    adapter_slots: int = 4           # FLAGS_adapter_slots (per rank class)

    def to_flags(self) -> Dict[str, object]:
        """The FLAGS_* assignment this candidate means (bucket sizes are
        DataParallel ctor args, surfaced under the same key the training
        entries read them back from)."""
        return {
            "dp_grad_comm_dtype": self.dp_comm_dtype,
            "dp_comm_block_size": int(self.dp_comm_block),
            "dp_shard_update": bool(self.dp_shard_update),
            "pp_schedule": self.pp_schedule,
            "pp_accumulate_steps": int(self.pp_microbatches),
            "pp_virtual_degree": int(self.pp_virtual_degree),
            "pallas_ffn": bool(self.pallas_ffn),
            "serving_token_budget": int(self.token_budget),
            "serving_max_batch": int(self.max_batch),
            "spec_k": int(self.spec_k),
            "adapter_slots": int(self.adapter_slots),
        }

    @classmethod
    def from_flags(cls, fl: Dict[str, object]) -> "Candidate":
        c = cls()
        m = {"dp_grad_comm_dtype": "dp_comm_dtype",
             "dp_comm_block_size": "dp_comm_block",
             "dp_shard_update": "dp_shard_update",
             "pp_schedule": "pp_schedule",
             "pp_accumulate_steps": "pp_microbatches",
             "pp_virtual_degree": "pp_virtual_degree",
             "pallas_ffn": "pallas_ffn",
             "serving_token_budget": "token_budget",
             "serving_max_batch": "max_batch",
             "spec_k": "spec_k",
             "adapter_slots": "adapter_slots"}
        kw = {m[k]: v for k, v in fl.items() if k in m}
        return replace(c, **kw) if kw else c

    def describe(self) -> str:
        """Short human label: only the fields that differ from default."""
        base = Candidate()
        diffs = [f"{f.name}={getattr(self, f.name)}" for f in fields(self)
                 if getattr(self, f.name) != getattr(base, f.name)]
        return ",".join(diffs) or "default"


@dataclass
class Ranked:
    candidate: Candidate
    predicted: dict                  # CostModel.predict output
    measured_s: Optional[float] = None

    @property
    def cost(self) -> float:
        return float(self.predicted["cost"])


def enumerate_space(axes: Dict[str, Sequence]) -> List[Candidate]:
    """Cartesian product over the given axes (Candidate field name ->
    values); unnamed fields stay at their defaults. The incumbent
    (``Candidate()``) is always included so the search can never regress
    below the hand-picked config."""
    names = sorted(axes)
    out = [Candidate()]
    seen = {out[0]}
    for combo in itertools.product(*(axes[n] for n in names)):
        c = replace(Candidate(), **dict(zip(names, combo)))
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def search(model: CostModel, workload: Workload,
           candidates: Iterable[Candidate],
           topk: Optional[int] = None,
           prune_ratio: Optional[float] = None) -> List[Ranked]:
    """Predict every candidate, prune against the analytic incumbent,
    return the top-k survivors ranked cheapest-first. Candidates whose
    prediction raises (e.g. an invalid schedule/microbatch combination)
    are dropped as infeasible, not fatal."""
    topk = int(topk if topk is not None else flags.flag_value("tune_topk"))
    prune_ratio = float(prune_ratio if prune_ratio is not None
                        else flags.flag_value("tune_prune_ratio"))
    ranked: List[Ranked] = []
    infeasible = 0
    for c in candidates:
        try:
            ranked.append(Ranked(c, model.predict(workload, c)))
        except (ValueError, KeyError):
            infeasible += 1
    if not ranked:
        raise ValueError("no feasible candidate in the search space")
    _emit("tuner.candidates", outcome="enumerated", n=len(ranked))
    if infeasible:
        _emit("tuner.candidates", outcome="infeasible", n=infeasible)
    incumbent = min(r.cost for r in ranked)
    survivors = [r for r in ranked if r.cost <= prune_ratio * incumbent]
    _emit("tuner.candidates", outcome="pruned",
          n=len(ranked) - len(survivors))
    survivors.sort(key=lambda r: r.cost)
    return survivors[:max(1, topk)]
