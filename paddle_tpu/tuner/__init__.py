"""Offline autotuner over the tunable-flag space.

The repo's config surface (dp bucket sizes + grad-comm dtype/block, pp
schedule x microbatches x virtual degree, ZeRO-1, the fused Pallas FFN,
serving token budget x max batch) grew hand-picked; this package turns
the three measurement sources that already exist — ``ci_op_benchmark``
op timings, ``schedule.simulate()`` bubbles, wire-byte accounting over
a measured link estimate — into a search loop:

1. :mod:`.cost_model` predicts a step time per candidate analytically;
2. :mod:`.search` enumerates the space and prunes everything whose
   analytic bound exceeds ``FLAGS_tune_prune_ratio`` x the incumbent;
3. :mod:`.profile` validates the top-k finalists with short real runs,
   pins the measured winner into a versioned CRC'd manifest per
   (model, topology), and applies it at startup via
   ``FLAGS_tuned_profile`` (the train-step factory and
   ``PagedServingEngine`` call :func:`maybe_apply_flagged`).

CI: ``tools/tune_smoke.py`` proves analytic top-1 = measured top-1 on a
toy space with zero steady-state retraces under the applied profile;
``tests/test_tuner.py`` pins the simulate-exact bubble model, the
prune-never-drops-the-winner guarantee and manifest fail-loudness.
"""
from __future__ import annotations

from ..core import flags

flags.define_flag(
    "tuned_profile", "",
    "Path to a tuned-profile manifest (tuner/profile.py). When set, "
    "make_train_step and PagedServingEngine apply its flag "
    "assignment at startup — before any executable is built, so the "
    "steady state under a profile performs zero retraces. Load, CRC "
    "and topology-mismatch failures raise (fail-loud).")
flags.define_flag(
    "tune_topk", 3,
    "Analytic finalists that get real validation runs per search.")
flags.define_flag(
    "tune_prune_ratio", 1.3,
    "Prune bound: candidates whose analytic cost exceeds this ratio x "
    "the analytic incumbent are never measured. The margin over 1.0 "
    "absorbs the cost model's own error so the measured winner is "
    "never pruned (tests/test_tuner.py pins this on a seeded space).")
flags.define_flag(
    "tune_validation_steps", 3,
    "Warm real steps measured per finalist during validation (median).")
flags.define_flag(
    "tune_link_bytes_per_s", 0.0,
    "Pinned link bandwidth (bytes/s) for the comm term; 0 measures a "
    "host->device transfer as the estimate (single-host proxy).")

from .cost_model import (BASELINE_PATH, CostModel, OpCosts,  # noqa: E402
                         Workload, entry_noise, entry_time,
                         estimate_link_bytes_per_s, machine_key)
from .profile import (PROFILE_FORMAT, PROFILE_VERSION,  # noqa: E402
                      TunedProfile, apply_profile, load_profile,
                      maybe_apply_flagged, save_profile,
                      topology_signature, tune, validate_candidates)
from .search import (Candidate, Ranked, enumerate_space,  # noqa: E402
                     search)

__all__ = [
    "BASELINE_PATH", "Candidate", "CostModel", "OpCosts", "Ranked",
    "TunedProfile", "Workload", "PROFILE_FORMAT", "PROFILE_VERSION",
    "apply_profile", "entry_noise", "entry_time", "enumerate_space",
    "estimate_link_bytes_per_s", "load_profile", "machine_key",
    "maybe_apply_flagged", "save_profile", "search",
    "topology_signature", "tune", "validate_candidates",
]
