"""paddle_tpu.observability — flight recorder + unified metrics registry.

One choke point, ``emit(kind, dur_s=None, **fields)``, feeds BOTH:

- the **flight recorder** (recorder.py): lock-free ring of the last
  ``FLAGS_flight_recorder_size`` events, serialized by dump-on-distress
  (watchdog timeout / fatal enforce / SIGUSR1) for post-mortem debugging;
- the **metrics registry** (metrics.py): counters/gauges/histograms with
  Prometheus text exposition and a JSON snapshot — the numbers behind
  ``profiler.dispatch_cache_stats()`` / ``async_stats()`` and the
  ci_op_benchmark overhead gate.

Fast path: ``FLAGS_metrics_sampling=0`` turns ``emit`` into a single
cached-int check and return (no tuple, no dict, no timestamps) — the
instrumented hot loops run at no-op-level overhead (budget: ≤3%, gated
by tools/ci_op_benchmark.py). ``=1`` (default) records everything;
``N>1`` keeps every metric EXACT but ring-records only every Nth
high-frequency event (dispatch hits, fetch stalls), bounding recorder
write traffic on multi-million-op runs.
"""
from __future__ import annotations

from typing import Optional

from ..core import flags
from .metrics import Registry, DEFAULT_BUCKETS  # noqa: F401
from .recorder import FlightRecorder

__all__ = ["emit", "enabled", "registry", "recorder", "reset", "summary",
           "fleet_summary", "prometheus_text", "metrics_snapshot",
           "dump_distress", "register_distress_section",
           "install_signal_handler", "Registry", "FlightRecorder"]

flags.define_flag("metrics_sampling", 1,
                  "Observability sampling: 0 disables emit() entirely "
                  "(metrics views freeze), 1 records everything, N>1 "
                  "ring-records 1/N of high-frequency events (metrics "
                  "stay exact)")
flags.define_flag("flight_recorder_size", 4096,
                  "Ring-buffer capacity (events) of the always-on flight "
                  "recorder")
flags.define_flag("log_retraces", False,
                  "Log the field-level signature diff explaining every "
                  "post-warmup dispatch-cache retrace to stderr")
flags.define_flag("distress_dir", "",
                  "Directory for dump-on-distress artifacts (default: "
                  "$PADDLE_DISTRESS_DIR, else the system temp dir)")
flags.define_flag("dump_on_enforce", False,
                  "Dump the flight recorder + metrics on EnforceNotMet "
                  "construction (rate-limited to 1/s)")

_registry = Registry()
_recorder = FlightRecorder(int(flags.flag_value("flight_recorder_size")))
# cached sampling knob: [0] = off, [1] = everything, [N] = 1/N ring writes
_sampling = [max(0, int(flags.flag_value("metrics_sampling")))]
_ring_tick = [0]

# high-frequency kinds subject to >1 ring sampling (metrics stay exact)
_HIGH_FREQ = frozenset({"dispatch.hit", "async.fetch_stall",
                        "async.enqueue", "async.p2p", "pipeline.send",
                        "pipeline.recv", "trace.span"})


def registry() -> Registry:
    return _registry


def recorder() -> FlightRecorder:
    return _recorder


def enabled() -> bool:
    return _sampling[0] > 0


def _on_flag_change(name: str, value):
    if name == "metrics_sampling":
        _sampling[0] = max(0, int(value))
    elif name == "flight_recorder_size":
        _recorder.resize(int(value))


flags.on_change(_on_flag_change)


# ---------------------------------------------------------------------------
# Metric fan-out: kind -> handler(dur_s, fields). Handlers close over their
# metric objects so a dispatch hit costs one dict lookup + one int add.
# ---------------------------------------------------------------------------

_C = _registry.counter
_G = _registry.gauge
_H = _registry.histogram

_c_hits = _C("paddle_dispatch_cache_hits_total",
             "Eager dispatch signature-cache hits")
_c_misses = _C("paddle_dispatch_cache_misses_total",
               "Eager dispatch signature-cache misses (probe runs)")
_c_bypasses = _C("paddle_dispatch_cache_bypasses_total",
                 "Dispatches that bypassed signature keying")
_c_neg = _C("paddle_dispatch_cache_negative_hits_total",
            "Dispatches short-circuited by the negative cache")
_c_evict = _C("paddle_dispatch_cache_evictions_total",
              "LRU evictions from the dispatch cache")
_c_poison = _C("paddle_dispatch_cache_poisoned_total",
               "Cached executables poisoned after a runtime failure")
_c_compiles = _C("paddle_compiles_total",
                 "Kernel (re)traces through the cached-executable builder")
_c_retraces = _C("paddle_retraces_total",
                 "Post-warmup dispatch-cache misses, by diffed reason")
_g_inflight = _G("paddle_eager_inflight_depth",
                 "Steps currently in flight in the async pipeline")
_g_maxdepth = _G("paddle_eager_inflight_depth_max",
                 "High-water mark of the in-flight queue")
_c_steps = _C("paddle_eager_steps_marked_total",
              "Step boundaries enqueued on the async pipeline")
_c_bp = _C("paddle_eager_backpressure_waits_total",
           "Host blocks caused by pipeline-depth backpressure")
_h_bp = _H("paddle_backpressure_wait_seconds",
           "Duration of pipeline backpressure waits")
_c_fetches = _C("paddle_eager_sync_fetches_total",
                "D2H scalar fetches (Tensor.numpy/.item sync points)")
_h_stall = _H("paddle_fetch_stall_seconds",
              "Host blocked time per D2H fetch, by stall")
_c_drains = _C("paddle_eager_drains_total",
               "Full pipeline drains (paddle.synchronize)")
_c_bwd = _C("paddle_backward_runs_total", "Autograd backward passes")
_h_bwd = _H("paddle_backward_seconds",
            "Host-side tape-walk time per backward pass")
_c_coll = _C("paddle_collectives_total", "Collectives issued, by op")
_h_coll = _H("paddle_collective_seconds",
             "Dispatch-to-complete duration of eager collectives")
_c_opt = _C("paddle_optimizer_steps_total",
            "Optimizer.step calls, by execution mode")
_h_opt = _H("paddle_optimizer_step_seconds", "Optimizer.step host time")
_c_nan = _C("paddle_nan_check_trips_total",
            "FLAGS_check_nan_inf trips, by op")
_c_tokens = _C("paddle_serving_tokens_total",
               "Tokens produced by the serving engine, by phase")
_h_chunk = _H("paddle_serving_chunk_seconds",
              "Serving prefill/decode-chunk dispatch durations")
_c_wd = _C("paddle_watchdog_timeouts_total",
           "Comm-watchdog timeout reports")
_c_enf = _C("paddle_enforce_errors_total",
            "EnforceNotMet errors raised, by type")
_c_dumps = _C("paddle_distress_dumps_total",
              "Dump-on-distress artifacts written, by reason")
_c_chaos = _C("paddle_chaos_injections_total",
              "Chaos-harness faults injected, by site and kind")
_c_store_retry = _C("paddle_store_retries_total",
                    "TCPStore reconnect+retry attempts, by op")
_c_coll_retry = _C("paddle_collective_retries_total",
                   "Collective retry attempts after retryable errors, by op")
_c_escalate = _C("paddle_watchdog_escalations_total",
                 "Watchdog policy-ladder stages applied, by stage")
_c_ckpt_saves = _C("paddle_ckpt_saves_total",
                   "Checkpoints published by CheckpointManager")
_c_ckpt_save_err = _C("paddle_ckpt_save_errors_total",
                      "CheckpointManager disk saves that failed")
_h_ckpt_save = _H("paddle_ckpt_save_seconds",
                  "Wall time of CheckpointManager disk saves")
_g_ckpt_step = _G("paddle_ckpt_last_step",
                  "Step of the newest published checkpoint")
_c_rollbacks = _C("paddle_ckpt_rollbacks_total",
                  "NaN/Inf step-guard rollbacks to last-good state")
_c_ckpt_loads = _C("paddle_ckpt_loads_total",
                   "CheckpointManager restores from disk")
_c_preempt = _C("paddle_preemption_flushes_total",
                "Final checkpoint flushes triggered by SIGTERM")
_c_coll_issue = _C("paddle_collective_issues_total",
                   "Collectives issued (pre-completion), by op; the gap "
                   "against paddle_collectives_total is in-flight or failed")
_c_aborts = _C("paddle_eager_aborts_total",
               "In-flight steps discarded by async-engine abort()")
_c_ckpt_gc = _C("paddle_ckpt_gc_total",
                "Old checkpoints removed by CheckpointManager retention GC")
_c_ckpt_hook_err = _C("paddle_ckpt_hook_errors_total",
                      "Step-boundary hook exceptions swallowed by "
                      "CheckpointManager")
_c_dp_comms = _C("paddle_dp_bucket_comms_total",
                 "DataParallel bucket collectives issued, by op")
_h_dp_comm = _H("paddle_dp_bucket_comm_seconds",
                "Issue-to-ready duration of DP bucket collectives")
_c_dp_reduced = _C("paddle_dp_bytes_reduced_total",
                   "Gradient bytes reduced (comm dtype) by the DP reducer")
_c_dp_gathered = _C("paddle_dp_bytes_gathered_total",
                    "Updated-param bytes all-gathered by the sharded update")
_g_dp_overlap = _G("paddle_dp_overlap_efficiency",
                   "Fraction of DP comm time hidden under backward "
                   "(1.0 = fully overlapped), last drain")
_c_dp_wire = _C("paddle_dp_wire_bytes_total",
                "Actual bytes placed on the DP gradient wire, by wire "
                "dtype (the int8 codec counts payload + block scales)")
_c_dp_wire_ref = _C("paddle_dp_wire_bytes_ref_total",
                    "Param-dtype-equivalent bytes of the same DP traffic; "
                    "ref/actual is the wire compression ratio")
_c_pp_wire = _C("paddle_pp_wire_bytes_total",
                "Actual bytes handed to pipeline P2P transfers, by wire "
                "dtype")
_c_pp_wire_ref = _C("paddle_pp_wire_bytes_ref_total",
                    "Payload-dtype-equivalent bytes of the same pipeline "
                    "handoffs; ref/actual is the wire compression ratio")
_c_dp_packs = _C("paddle_dp_flat_pack_calls_total",
                 "Cached flat pack/unpack executable invocations")
_c_dp_builds = _C("paddle_dp_flat_pack_builds_total",
                  "Bucket-plan/executable builds (steady state: constant)")
_c_srv_req = _C("paddle_serving_requests_total",
                "Serving request lifecycle events, by event (admitted/"
                "completed/preempted/shed/deadline/cancelled)")
_h_srv_ttft = _H("paddle_serving_ttft_seconds",
                 "Time-to-first-token: submit to first streamed token")
_h_srv_tpot = _H("paddle_serving_tpot_seconds",
                 "Time-per-output-token: inter-token gap after the first")
_h_srv_step = _H("paddle_serving_step_seconds",
                 "Fused mixed prefill+decode step dispatch durations")
_g_srv_queue = _G("paddle_serving_queue_depth",
                  "Requests waiting for admission")
_g_srv_running = _G("paddle_serving_running",
                    "Requests currently holding KV blocks / batch slots")
_g_srv_util = _G("paddle_serving_kv_block_utilization",
                 "Fraction of the paged KV block pool in use")
_c_srv_steps = _C("paddle_serving_steps_total",
                  "Fused serving steps dispatched")
_c_srv_builds = _C("paddle_serving_step_builds_total",
                   "Serving step executable (re)builds — steady state: "
                   "constant (zero retraces)")
_c_srv_prefix = _C("paddle_serving_prefix_cached_tokens_total",
                   "Prompt tokens served from the paged prefix cache "
                   "instead of recompute")
_c_srv_cow = _C("paddle_serving_cow_copies_total",
                "Copy-on-write KV page copies executed on device")
_c_srv_blocks = _C("paddle_serving_blocks_committed_total",
                   "Blocks of a block-diffusion model committed (their "
                   "commit forward done, their tokens streamed)")
_c_srv_pallas = _C("paddle_serving_pallas_steps_total",
                   "Serving steps served through the Pallas paged-attention "
                   "kernel, by kind (decode = max_q=1 specialized launch, "
                   "mixed = generic ragged launch)")
_c_ffn = _C("paddle_pallas_ffn_steps_total",
            "Steps served through the fused Pallas SwiGLU FFN kernel, by "
            "kind (serving = engine tick with fused FFN, fused_tick = the "
            "mega-kernelized decode tick: paged attention + fused FFN + "
            "one-launch sampler prep)")
_c_ffn_fb = _C("paddle_pallas_ffn_fallback_total",
               "Steps that wanted FLAGS_pallas_ffn but served the stock "
               "XLA FFN instead, by reason (unavailable = no TPU, "
               "unsupported = shape outside the kernel plan, quant = "
               "activation-quantized leaves the kernel does not cover)")
_c_elastic = _C("paddle_elastic_events_total",
                "Elastic-runtime lifecycle events, by kind (start/"
                "rank_dead/epoch_bump/reconfigure/rejoin/refuse/...)")
_g_elastic_world = _G("paddle_elastic_world_size",
                      "Live world size as of the last elastic event")
_h_elastic_reconf = _H("paddle_elastic_reconfigure_seconds",
                       "Wall time of elastic world reconfigurations "
                       "(epoch bump to resharded state published)")
_c_rt_admit = _C("paddle_router_admitted_total",
                 "Streams admitted by the serving router, by tenant")
_c_rt_shed = _C("paddle_router_shed_total",
                "Streams shed by the router, by tenant and reason")
_c_rt_complete = _C("paddle_router_completed_total",
                    "Router streams finished, by tenant and reason")
_c_rt_assign = _C("paddle_router_assignments_total",
                  "Stream placements onto replicas (failover replays "
                  "and drain migrations place again)")
_c_rt_prefix = _C("paddle_router_prefix_routed_total",
                  "Placements chosen by prompt-prefix affinity rather "
                  "than least-loaded fallback")
_c_rt_failover = _C("paddle_router_failovers_total",
                    "Streams failed over after a replica death, by "
                    "tenant")
_c_rt_migrate = _C("paddle_router_migrations_total",
                   "Streams migrated off a draining replica, by tenant")
_c_rt_readmit = _C("paddle_router_readmits_total",
                   "Dead replicas re-admitted on probation")
_c_rt_drain = _C("paddle_router_drains_total",
                 "Graceful replica drains initiated")
_c_rt_mismatch = _C("paddle_router_failover_mismatches_total",
                    "Failover replays that diverged from the already-"
                    "streamed prefix (determinism violations)")
_c_rt_state = _C("paddle_router_replica_state_changes_total",
                 "Replica circuit-breaker transitions, by new state")
_g_rt_replicas = _G("paddle_router_replicas",
                    "Replica count by circuit-breaker state")
_g_rt_util = _G("paddle_router_replica_kv_utilization",
                "Per-replica paged KV pool utilization")
_g_rt_pending = _G("paddle_router_pending_requests",
                   "Router-side requests awaiting placement")
_g_rt_live = _G("paddle_router_live_streams",
                "Streams admitted and not yet finished")
_c_mig_handoffs = _C("paddle_migration_handoffs_total",
                     "Disagg prefill→decode handoffs, by result (ok = "
                     "pages pulled and adopted, local = same-replica "
                     "shortcut, fallback = decode-side recompute)")
_c_mig_pages = _C("paddle_migration_pages_total",
                  "KV pages shipped over the migration page transport")
_c_mig_bytes = _C("paddle_migration_wire_bytes_total",
                  "Bytes offered to the migration page transport, by "
                  "wire encoding")
_c_mig_retries = _C("paddle_migration_retries_total",
                    "Migration page-pull retries (typed timeout + capped "
                    "exponential backoff)")
_c_mig_fallbacks = _C("paddle_migration_fallbacks_total",
                      "Handoffs degraded to decode-side prefill "
                      "recompute, by reason (timeout/stale_epoch/"
                      "corrupt/mismatch/...)")
_c_mig_mono = _C("paddle_migration_monolithic_trips_total",
                 "Sustained-migration-failure trips back to monolithic "
                 "same-replica serving")
_c_as_decisions = _C("paddle_autoscaler_decisions_total",
                     "SLO autoscaler decisions, by direction "
                     "(grow/shrink/hold)")
_g_as_pool = _G("paddle_autoscaler_decode_pool",
                "Accepting decode-pool replicas as of the last "
                "autoscaler tick")
_c_tune_cand = _C("paddle_tuner_candidates_total",
                  "Autotuner candidates, by outcome (enumerated/pruned/"
                  "infeasible/measured)")
_g_tune_pred = _G("paddle_tuner_predicted_step_seconds",
                  "Analytic cost of the last validated tuner finalist")
_g_tune_meas = _G("paddle_tuner_measured_step_seconds",
                  "Measured step time of the last validated tuner "
                  "finalist")
_g_tune_gap = _G("paddle_tuner_gap_ratio",
                 "measured/predicted of the last validated tuner "
                 "finalist — the cost model's live calibration error")
_c_tune_profile = _C("paddle_tuner_profile_loads_total",
                     "Tuned-profile load attempts, by result (ok/applied/"
                     "crc_mismatch/bad_version/bad_format/parse_error/"
                     "topology_mismatch)")
_c_tune_predicts = _C("paddle_tuner_predictions_total",
                      "Cost-model candidate predictions issued")
_c_tune_runs = _C("paddle_tuner_runs_total",
                  "End-to-end tune() searches completed")
_h_tune_run = _H("paddle_tuner_run_seconds",
                 "Wall time of one end-to-end tune() search")
_c_pp_sends = _C("paddle_pp_sends_total",
                 "Pipeline stage handoffs issued (activation/grad), by kind")
_h_pp_send = _H("paddle_pp_send_seconds",
                "Host-side issue latency of pipeline P2P handoffs")
_c_pp_recvs = _C("paddle_pp_recvs_total",
                 "Pipeline stage inputs consumed, by kind and readiness")
_c_pp_stalls = _C("paddle_pp_stalls_total",
                  "Stage actions that had to wait for an upstream producer")
_c_pp_builds = _C("paddle_pp_stage_builds_total",
                  "Per-stage executable builds (signature-cache misses); "
                  "constant after warmup = zero steady-state retraces")
_c_pp_runs = _C("paddle_pp_runs_total",
                "Pipeline engine batch runs, by schedule")
_g_pp_bubble = _G("paddle_pp_bubble_fraction",
                  "Schedule bubble fraction of the last pipeline run "
                  "(idle device-slots / total device-slots)")
_g_pp_skew = _G("paddle_pp_stage_skew",
                "Stage host-dispatch-time imbalance of the last run "
                "((max - mean) / mean)")
_c_p2p = _C("paddle_eager_p2p_transfers_total",
            "Async device-to-device transfers issued through the eager "
            "pipeline")
_c_ckpt_reshard = _C("paddle_ckpt_pp_reshards_total",
                     "Checkpoint reshards across a changed pipeline degree")
_c_q_calib = _C("paddle_quant_calibration_runs_total",
                "PTQ calibration passes completed (quant manifests built)")
_c_q_mm = _C("paddle_quant_matmuls_total",
             "Transformer matmuls swapped to quantized executables by the "
             "model transform, by mode (w8/w8a8/fp8)")
_c_q_kv_q = _C("paddle_quant_kv_quant_tokens_total",
               "Token-layer KV entries quantized to int8 pages on append")
_c_q_kv_dq = _C("paddle_quant_kv_dequant_pages_total",
                "Page-layer int8 KV reads dequantized inside the paged "
                "attention step")
_c_q_manifest = _C("paddle_quant_manifest_loads_total",
                   "Quant manifest load attempts, by result (ok/"
                   "crc_mismatch/bad_version/bad_format/parse_error)")
_g_srv_bytes = _G("paddle_serving_kv_bytes_in_use",
                  "Device bytes behind allocated KV pages (dtype-aware; "
                  "int8 pages count their real footprint)")
_g_srv_bytes_total = _G("paddle_serving_kv_bytes_total",
                        "Device bytes of the whole KV page pool")
_c_tr_spans = _C("paddle_trace_spans_total",
                 "Finished trace spans, by span name (tracing.py)")
_h_tr_span = _H("paddle_trace_span_seconds",
                "Finished trace-span durations (all span names)")
_g_tr_active = _G("paddle_trace_active_spans",
                  "Spans currently open on this process (in-flight "
                  "requests/steps land in distress dumps from here)")
_c_fl_pub = _C("paddle_fleet_publishes_total",
               "Registry snapshots published to the fleet metrics plane")
_h_fl_pub = _H("paddle_fleet_publish_seconds",
               "Serialize+store latency of a fleet snapshot publish")
_c_fl_merge = _C("paddle_fleet_merges_total",
                 "Fleet aggregations performed (fleet_summary calls)")
_g_fl_ranks = _G("paddle_fleet_ranks",
                 "Snapshots merged into the last fleet aggregation")
_g_fl_ttft50 = _G("paddle_fleet_ttft_p50_seconds",
                  "Fleet-global TTFT p50 from the last aggregation")
_g_fl_ttft99 = _G("paddle_fleet_ttft_p99_seconds",
                  "Fleet-global TTFT p99 from the last aggregation")
_g_fl_tpot50 = _G("paddle_fleet_tpot_p50_seconds",
                  "Fleet-global TPOT p50 from the last aggregation")
_g_fl_tpot99 = _G("paddle_fleet_tpot_p99_seconds",
                  "Fleet-global TPOT p99 from the last aggregation")
_g_fl_shed = _G("paddle_fleet_shed_rate",
                "Fleet-global shed fraction from the last aggregation")
_g_pp_mbubble = _G("paddle_pp_measured_bubble_fraction",
                   "MEASURED bubble fraction of the last pipeline run "
                   "(host action timeline, vs the simulate() prediction)")
_g_pp_bgap = _G("paddle_pp_bubble_gap",
                "measured - predicted bubble fraction of the last run "
                "(schedule conformance: ~0 when reality matches the sim)")
_g_pp_strag = _G("paddle_pp_straggler_stage",
                 "Physical stage group with the most measured busy time "
                 "in the last pipeline run")
_g_pp_strag_x = _G("paddle_pp_straggler_excess",
                   "Straggler group's busy-time excess over the mean "
                   "((max - mean) / mean) in the last run")
_c_ad_reg = _C("paddle_adapter_registered_total",
               "LoRA adapters registered with an AdapterManager")
_c_ad_loads = _C("paddle_adapter_loads_total",
                 "Adapter device loads (host pack -> stacked slot pack)")
_c_ad_swaps = _C("paddle_adapter_swaps_total",
                 "Adapter device RE-loads (hot-swap churn: the adapter "
                 "had been resident before and is loading again)")
_c_ad_evict = _C("paddle_adapter_evictions_total",
                 "Adapter device evictions, by reason (lru/manual/"
                 "replace/chaos)")
_c_ad_hits = _C("paddle_adapter_hits_total",
                "Adapter uses served by an already-resident slot")
_c_ad_manifest = _C("paddle_adapter_manifest_loads_total",
                    "Adapter manifest load attempts, by result (ok/"
                    "crc_mismatch/bad_version/bad_format/parse_error/"
                    "signature_mismatch)")
_c_ad_prefetch = _C("paddle_adapter_prefetches_total",
                    "Adapter store-transport prefetches, by result "
                    "(ok/registered/miss/corrupt)")
_g_ad_resident = _G("paddle_adapter_resident",
                    "Adapters currently holding a device slot")
_g_ad_bytes = _G("paddle_adapter_bytes_in_use",
                 "Device bytes behind occupied adapter slots (also folded "
                 "into paddle_serving_kv_bytes_in_use via the block "
                 "manager's extra-bytes callback)")
_g_ad_bytes_total = _G("paddle_adapter_bytes_total",
                       "Device bytes of all allocated adapter slot packs")
_g_ad_res_by = _G("paddle_adapter_device_resident",
                  "1 while the labeled adapter holds a device slot on "
                  "this process, 0 after eviction (fleet_summary counts "
                  "rank-labeled 1s into per-adapter residency)")
_c_spec_ticks = _C("paddle_spec_ticks_total",
                   "Speculative verify ticks (one widened decode chunk)")
_c_spec_prop = _C("paddle_spec_proposed_total",
                  "Draft tokens proposed for verification")
_c_spec_acc = _C("paddle_spec_accepted_total",
                 "Draft tokens accepted by greedy verification")
_c_spec_bonus = _C("paddle_spec_bonus_total",
                   "Bonus tokens emitted by verify ticks (one per tick — "
                   "the tick's output even at zero acceptance)")
_c_spec_draft = _C("paddle_spec_draft_steps_total",
                   "Draft-model device steps (catch-up chunks + 1-token "
                   "proposal steps)")
_g_spec_rate = _G("paddle_spec_acceptance_rate",
                  "accepted/proposed over the process lifetime (the "
                  "speculation speedup signal: tokens/tick ~ 1 + rate*k)")


# hit-path fast handler: one dict op, no Counter.inc/_label_key calls.
# Counter.reset() clears _values in place, so the bound dict stays live.
_hits_values = _c_hits._values


def _h_dispatch_hit(dur_s, f):
    _hits_values[()] = _hits_values.get((), 0) + 1


def _h_dispatch_miss(dur_s, f):
    _c_misses.inc()


def _h_retrace(dur_s, f):
    _c_retraces.inc(labels={"op": f.get("op", ""),
                            "reason": f.get("reason", "unknown")})


def _h_enqueue(dur_s, f):
    d = f.get("depth", 0)
    _g_inflight.set(d)
    _g_maxdepth.set_max(d)
    _c_steps.inc()


def _h_backpressure(dur_s, f):
    _c_bp.inc()
    if dur_s is not None:
        _h_bp.observe(dur_s)


def _h_fetch(dur_s, f):
    _c_fetches.inc()
    if dur_s is not None:
        _h_stall.observe(dur_s)


def _h_depth(dur_s, f):
    _g_inflight.set(f.get("depth", 0))


def _h_backward(dur_s, f):
    _c_bwd.inc()
    if dur_s is not None:
        _h_bwd.observe(dur_s)


def _h_collective(dur_s, f):
    _c_coll.inc(labels={"op": f.get("op", "")})
    if dur_s is not None:
        _h_coll.observe(dur_s)


def _h_optimizer(dur_s, f):
    _c_opt.inc(labels={"mode": f.get("mode", "")})
    if dur_s is not None:
        _h_opt.observe(dur_s)


def _h_serving(phase):
    def h(dur_s, f):
        _c_tokens.inc(f.get("tokens", 0), labels={"phase": phase})
        if dur_s is not None:
            _h_chunk.observe(dur_s)
    return h


def _h_srv_event(event):
    def h(dur_s, f):
        _c_srv_req.inc(labels={"event": event})
    return h


def _h_srv_shed(dur_s, f):
    # one kind covers both shed flavors: queue overflow and deadline expiry
    event = "deadline" if f.get("reason") == "deadline" else "shed"
    _c_srv_req.inc(labels={"event": event})


def _h_srv_step_h(dur_s, f):
    """The engine's one event a tick: its fields are the `serve.tick`
    span's (the span's `kind` as `tick_kind`: an event's `kind` is its
    name), with `pallas` / `ffn` (what the build decided once)."""
    _c_srv_steps.inc()
    _c_tokens.inc(f.get("tokens", 0), labels={"phase": "mixed"})
    if dur_s is not None:
        _h_srv_step.observe(dur_s)
    decode = f.get("tick_kind") == "decode"
    if f.get("pallas"):
        _c_srv_pallas.inc(labels={"kind": "decode" if decode else "mixed"})
    if f.get("ffn"):
        _c_ffn.inc(labels={"kind": "fused_tick" if decode else "serving"})


def _h_srv_token(dur_s, f):
    ttft, tpot = f.get("ttft_s"), f.get("tpot_s")
    if ttft is not None:
        _h_srv_ttft.observe(ttft)
    if tpot is not None:
        _h_srv_tpot.observe(tpot)


def _h_srv_gauges(dur_s, f):
    _g_srv_queue.set(f.get("queue_depth", 0))
    _g_srv_running.set(f.get("running", 0))
    _g_srv_util.set(f.get("kv_utilization", 0.0))
    if "kv_bytes_in_use" in f:
        _g_srv_bytes.set(f.get("kv_bytes_in_use", 0))
        _g_srv_bytes_total.set(f.get("kv_bytes_total", 0))


def _h_pp_send_h(dur_s, f):
    _c_pp_sends.inc(labels={"kind": f.get("payload", "act")})
    if dur_s is not None:
        _h_pp_send.observe(dur_s)


def _h_pp_recv(dur_s, f):
    _c_pp_recvs.inc(labels={"kind": f.get("payload", "act"),
                            "ready": str(bool(f.get("ready", True)))})


def _h_pp_gauges(dur_s, f):
    _g_pp_bubble.set(f.get("bubble_fraction", 0.0))
    _g_pp_skew.set(f.get("stage_skew", 0.0))
    if "measured_bubble_fraction" in f:
        _g_pp_mbubble.set(f["measured_bubble_fraction"])
        _g_pp_bgap.set(f.get("bubble_gap", 0.0))
        _g_pp_strag.set(f.get("straggler_group", 0))
        _g_pp_strag_x.set(f.get("straggler_excess", 0.0))


def _h_trace_span(dur_s, f):
    _c_tr_spans.inc(labels={"name": f.get("name", "")})
    _g_tr_active.set(f.get("active", 0))
    if dur_s is not None:
        _h_tr_span.observe(dur_s)


def _h_fleet_slo(dur_s, f):
    _g_fl_ttft50.set(f.get("ttft_p50", 0.0))
    _g_fl_ttft99.set(f.get("ttft_p99", 0.0))
    _g_fl_tpot50.set(f.get("tpot_p50", 0.0))
    _g_fl_tpot99.set(f.get("tpot_p99", 0.0))
    _g_fl_shed.set(f.get("shed_rate", 0.0))


def _h_rt_assign(dur_s, f):
    _c_rt_assign.inc()
    if f.get("prefix_hit", 0) > 0:
        _c_rt_prefix.inc()


def _h_rt_gauges(dur_s, f):
    _g_rt_pending.set(f.get("pending", 0))
    _g_rt_live.set(f.get("live_streams", 0))
    for state in ("healthy", "degraded", "dead", "draining", "drained"):
        _g_rt_replicas.set(f.get(state, 0), labels={"state": state})


def _h_mig_pages(dur_s, f):
    _c_mig_pages.inc(f.get("pages", 0))
    _c_mig_bytes.inc(f.get("bytes", 0),
                     labels={"wire": f.get("wire", "raw")})


def _h_as_decision(dur_s, f):
    _c_as_decisions.inc(labels={"direction": f.get("direction", "hold")})
    _g_as_pool.set(f.get("pool", 0))


def _h_tuner_validate(dur_s, f):
    _g_tune_pred.set(f.get("predicted_s", 0.0))
    _g_tune_meas.set(f.get("measured_s", 0.0))
    _g_tune_gap.set(f.get("gap_ratio", 0.0))


def _h_ad_load(dur_s, f):
    name = f.get("adapter", "")
    _c_ad_loads.inc(labels={"adapter": name})
    _g_ad_res_by.set(1, labels={"adapter": name})
    if f.get("swap"):
        _c_ad_swaps.inc(labels={"adapter": name})


def _h_ad_evict(dur_s, f):
    _c_ad_evict.inc(labels={"reason": f.get("reason", "lru")})
    _g_ad_res_by.set(0, labels={"adapter": f.get("adapter", "")})


def _h_ad_gauges(dur_s, f):
    _g_ad_resident.set(f.get("resident", 0))
    _g_ad_bytes.set(f.get("bytes_in_use", 0))
    _g_ad_bytes_total.set(f.get("bytes_total", 0))


def _h_spec_tick(dur_s, f):
    _c_spec_ticks.inc()
    _c_spec_prop.inc(f.get("proposed", 0))
    _c_spec_acc.inc(f.get("accepted", 0))
    _c_spec_bonus.inc()
    prop = _c_spec_prop.value()
    if prop:
        _g_spec_rate.set(round(_c_spec_acc.value() / prop, 4))


_HANDLERS = {
    "dispatch.hit": _h_dispatch_hit,
    "dispatch.miss": _h_dispatch_miss,
    "dispatch.bypass": lambda d, f: _c_bypasses.inc(),
    "dispatch.negative_hit": lambda d, f: _c_neg.inc(),
    "dispatch.eviction": lambda d, f: _c_evict.inc(),
    "dispatch.poisoned": lambda d, f: _c_poison.inc(),
    "dispatch.compile": lambda d, f: _c_compiles.inc(),
    "dispatch.retrace": _h_retrace,
    "async.enqueue": _h_enqueue,
    "async.depth": _h_depth,
    "async.backpressure": _h_backpressure,
    "async.fetch_stall": _h_fetch,
    # depth-0 forced-sync block: stalls the host like a fetch (feeds the
    # stall histogram) but is not a D2H scalar fetch (no counter bump)
    "async.sync_wait": lambda d, f: (_h_stall.observe(d)
                                     if d is not None else None),
    "async.drain": lambda d, f: _c_drains.inc(),
    "async.abort": lambda d, f: _c_aborts.inc(f.get("n_steps", 0)),
    "backward": _h_backward,
    "collective.complete": _h_collective,
    "collective.issue": lambda d, f: _c_coll_issue.inc(
        labels={"op": f.get("op", "")}),
    "collective.gang_restart": lambda d, f: _c_elastic.inc(
        labels={"kind": "gang_restart"}),
    "optimizer.step": _h_optimizer,
    "nan_check.trip": lambda d, f: _c_nan.inc(
        labels={"op": f.get("op", "")}),
    "serving.prefill": _h_serving("prefill"),
    "serving.decode_chunk": _h_serving("decode"),
    "serving.admit": _h_srv_event("admitted"),
    "serving.complete": _h_srv_event("completed"),
    "serving.preempt": _h_srv_event("preempted"),
    "serving.cancel": _h_srv_event("cancelled"),
    "serving.shed": _h_srv_shed,
    "serving.step": _h_srv_step_h,
    "serving.step_build": lambda d, f: _c_srv_builds.inc(),
    "serving.prefix_hit": lambda d, f: _c_srv_prefix.inc(
        f.get("tokens", 0)),
    "serving.cow": lambda d, f: _c_srv_cow.inc(f.get("copies", 1)),
    "serving.block_commit": lambda d, f: _c_srv_blocks.inc(),
    "pallas_ffn.fallback": lambda d, f: _c_ffn_fb.inc(
        labels={"reason": f.get("reason", "")}),
    "serving.token": _h_srv_token,
    "serving.gauges": _h_srv_gauges,
    "router.admit": lambda d, f: _c_rt_admit.inc(
        labels={"tenant": f.get("tenant", "")}),
    "router.shed": lambda d, f: _c_rt_shed.inc(
        labels={"tenant": f.get("tenant", ""),
                "reason": f.get("reason", "queue_full")}),
    "router.complete": lambda d, f: _c_rt_complete.inc(
        labels={"tenant": f.get("tenant", ""),
                "reason": f.get("reason", "")}),
    "router.assign": _h_rt_assign,
    "router.failover": lambda d, f: _c_rt_failover.inc(
        labels={"tenant": f.get("tenant", "")}),
    "router.migrate": lambda d, f: _c_rt_migrate.inc(
        labels={"tenant": f.get("tenant", "")}),
    "router.readmit": lambda d, f: _c_rt_readmit.inc(),
    "router.drain": lambda d, f: _c_rt_drain.inc(),
    "router.mismatch": lambda d, f: _c_rt_mismatch.inc(),
    "router.replica_state": lambda d, f: _c_rt_state.inc(
        labels={"state": f.get("state", "")}),
    "router.replica": lambda d, f: _g_rt_util.set(
        f.get("kv_utilization", 0.0),
        labels={"replica": str(f.get("replica", ""))}),
    "router.gauges": _h_rt_gauges,
    "migration.handoff": lambda d, f: _c_mig_handoffs.inc(
        labels={"result": f.get("result", "")}),
    "migration.pages": _h_mig_pages,
    "migration.retry": lambda d, f: _c_mig_retries.inc(),
    "migration.fallback": lambda d, f: _c_mig_fallbacks.inc(
        labels={"reason": f.get("reason", "")}),
    "migration.monolithic": lambda d, f: _c_mig_mono.inc(),
    "autoscale.decision": _h_as_decision,
    "tuner.candidates": lambda d, f: _c_tune_cand.inc(
        f.get("n", 1), labels={"outcome": f.get("outcome", "enumerated")}),
    "tuner.validate": _h_tuner_validate,
    "tuner.predict": lambda d, f: _c_tune_predicts.inc(),
    "tuner.tune": lambda d, f: (_c_tune_runs.inc(),
                                _h_tune_run.observe(f.get("dur_s", d)
                                                    or 0.0)),
    "tuner.profile_load": lambda d, f: _c_tune_profile.inc(
        labels={"result": f.get("result", "")}),
    "async.p2p": lambda d, f: _c_p2p.inc(),
    "pipeline.send": _h_pp_send_h,
    "pipeline.recv": _h_pp_recv,
    "pipeline.stall": lambda d, f: _c_pp_stalls.inc(),
    "pipeline.build": lambda d, f: _c_pp_builds.inc(),
    "pipeline.run": lambda d, f: _c_pp_runs.inc(
        labels={"schedule": f.get("schedule", "")}),
    "pipeline.gauges": _h_pp_gauges,
    "ckpt.reshard_pp": lambda d, f: _c_ckpt_reshard.inc(),
    "watchdog.timeout": lambda d, f: _c_wd.inc(),
    "watchdog.escalate": lambda d, f: _c_escalate.inc(
        labels={"stage": f.get("stage", "")}),
    "chaos.inject": lambda d, f: _c_chaos.inc(
        labels={"site": f.get("site", ""), "kind": f.get("fault", "")}),
    "store.retry": lambda d, f: _c_store_retry.inc(
        labels={"op": f.get("op", "")}),
    "collective.retry": lambda d, f: _c_coll_retry.inc(
        labels={"op": f.get("op", "")}),
    "ckpt.save": lambda d, f: (_c_ckpt_saves.inc(),
                               _g_ckpt_step.set(f.get("step", 0)),
                               _h_ckpt_save.observe(d)
                               if d is not None else None),
    "ckpt.save_error": lambda d, f: _c_ckpt_save_err.inc(),
    "ckpt.rollback": lambda d, f: _c_rollbacks.inc(),
    "ckpt.load": lambda d, f: _c_ckpt_loads.inc(),
    "ckpt.preempt": lambda d, f: _c_preempt.inc(),
    "ckpt.gc": lambda d, f: _c_ckpt_gc.inc(),
    "ckpt.hook_error": lambda d, f: _c_ckpt_hook_err.inc(),
    "dp.bucket_comm": lambda d, f: (
        _c_dp_comms.inc(labels={"op": f.get("op", "")}),
        _c_dp_reduced.inc(f.get("bytes", 0)),
        _h_dp_comm.observe(d) if d is not None else None),
    "dp.gather": lambda d, f: _c_dp_gathered.inc(f.get("bytes", 0)),
    "dp.wire": lambda d, f: (
        _c_dp_wire.inc(f.get("bytes", 0),
                       labels={"dtype": f.get("dtype", "")}),
        _c_dp_wire_ref.inc(f.get("ref_bytes", 0))),
    "pp.wire": lambda d, f: (
        _c_pp_wire.inc(f.get("bytes", 0),
                       labels={"dtype": f.get("dtype", "")}),
        _c_pp_wire_ref.inc(f.get("ref_bytes", 0))),
    "dp.overlap": lambda d, f: _g_dp_overlap.set(f.get("efficiency", 0.0)),
    "dp.pack_call": lambda d, f: _c_dp_packs.inc(),
    "dp.pack_build": lambda d, f: _c_dp_builds.inc(),
    "dp.reshard": lambda d, f: _c_elastic.inc(labels={"kind": "reshard"}),
    "elastic.event": lambda d, f: _c_elastic.inc(
        labels={"kind": f.get("event", "")}),
    "elastic.world": lambda d, f: _g_elastic_world.set(f.get("world", 0)),
    "elastic.reconfigure": lambda d, f: (
        _c_elastic.inc(labels={"kind": "reconfigure"}),
        _g_elastic_world.set(f.get("world", 0)),
        _h_elastic_reconf.observe(d) if d is not None else None),
    "enforce.error": lambda d, f: _c_enf.inc(
        labels={"type": f.get("type", "")}),
    "distress.dump": lambda d, f: _c_dumps.inc(
        labels={"reason": f.get("reason", "")}),
    "quant.calibrate": lambda d, f: _c_q_calib.inc(),
    "quant.convert": lambda d, f: _c_q_mm.inc(
        f.get("matmuls", 0), labels={"mode": f.get("mode", "")}),
    "quant.kv_step": lambda d, f: (_c_q_kv_q.inc(f.get("tokens", 0)),
                                   _c_q_kv_dq.inc(f.get("pages", 0))),
    "quant.manifest_load": lambda d, f: _c_q_manifest.inc(
        labels={"result": f.get("result", "")}),
    "trace.span": _h_trace_span,
    "fleet.publish": lambda d, f: (_c_fl_pub.inc(),
                                   _h_fl_pub.observe(d)
                                   if d is not None else None),
    "fleet.merge": lambda d, f: (_c_fl_merge.inc(),
                                 _g_fl_ranks.set(f.get("ranks", 0))),
    "fleet.slo": _h_fleet_slo,
    "adapter.register": lambda d, f: _c_ad_reg.inc(),
    "adapter.load": _h_ad_load,
    "adapter.use": lambda d, f: _c_ad_hits.inc(
        labels={"adapter": f.get("adapter", "")}),
    "adapter.evict": _h_ad_evict,
    "adapter.manifest_load": lambda d, f: _c_ad_manifest.inc(
        labels={"result": f.get("result", "")}),
    "adapter.prefetch": lambda d, f: _c_ad_prefetch.inc(
        labels={"result": f.get("result", "")}),
    "adapter.gauges": _h_ad_gauges,
    "spec.tick": _h_spec_tick,
    "spec.draft_step": lambda d, f: _c_spec_draft.inc(),
}


def emit(kind: str, dur_s: Optional[float] = None,
         # default-arg bindings skip global lookups on the hot path; all
         # referenced objects are mutated in place, never rebound
         _s=_sampling, _get=_HANDLERS.get, _record=_recorder.record,
         _hf=_HIGH_FREQ, _tick=_ring_tick, **fields):
    """The single instrumentation choke point. See module docstring for
    the FLAGS_metrics_sampling fast path."""
    s = _s[0]
    if not s:
        return
    h = _get(kind)
    if h is not None:
        h(dur_s, fields)
    if s > 1 and kind in _hf:
        _tick[0] += 1
        if _tick[0] % s:
            return
    _record(kind, dur_s, fields or None)


# ---------------------------------------------------------------------------
# Views / exports
# ---------------------------------------------------------------------------

def metrics_snapshot() -> dict:
    return _registry.snapshot()


def prometheus_text() -> str:
    return _registry.prometheus_text()


def _ratio(ref, actual) -> float:
    """Wire compression ratio (ref/actual bytes); 0.0 before any traffic."""
    return round(float(ref) / float(actual), 4) if actual else 0.0


def summary() -> dict:
    """The perf-triage digest printed by tools:
    dispatch hit-rate, retrace count, fetch-stall p50/p99."""
    hits = _c_hits.value()
    misses = _c_misses.value()
    neg = _c_neg.value()
    total = hits + misses + neg
    return {
        "dispatch_hit_rate": round(hits / total, 4) if total else 0.0,
        "dispatch_hits": int(hits),
        "dispatch_misses": int(misses),
        "retraces_total": int(_c_retraces.value()),
        "compiles_total": int(_c_compiles.value()),
        "fetch_stalls_total": int(_c_fetches.value()),
        "fetch_stall_p50_s": round(_h_stall.percentile(50), 6),
        "fetch_stall_p99_s": round(_h_stall.percentile(99), 6),
        "backpressure_waits": int(_c_bp.value()),
        "max_inflight_depth": int(_g_maxdepth.value()),
        "dp_bucket_comms": int(_c_dp_comms.value()),
        "dp_bytes_reduced": int(_c_dp_reduced.value()),
        "dp_bytes_gathered": int(_c_dp_gathered.value()),
        "dp_overlap_efficiency": round(float(_g_dp_overlap.value()), 4),
        "dp_flat_pack_builds": int(_c_dp_builds.value()),
        "dp": {
            "wire_bytes": int(_c_dp_wire.value()),
            "wire_bytes_ref": int(_c_dp_wire_ref.value()),
            "wire_compression_ratio": _ratio(
                _c_dp_wire_ref.value(), _c_dp_wire.value()),
        },
        "events_recorded": _recorder.written(),
        "elastic": {
            "reconfigurations": int(_c_elastic.value(
                {"kind": "reconfigure"})),
            "rank_deaths": int(_c_elastic.value({"kind": "rank_dead"})),
            "rejoins": int(_c_elastic.value({"kind": "rejoin"})),
            "world_size": int(_g_elastic_world.value()),
            "reconfigure_p50_s": round(_h_elastic_reconf.percentile(50), 6),
            "reconfigure_p99_s": round(_h_elastic_reconf.percentile(99), 6),
        },
        "serving": {
            "admitted": int(_c_srv_req.value({"event": "admitted"})),
            "completed": int(_c_srv_req.value({"event": "completed"})),
            "preempted": int(_c_srv_req.value({"event": "preempted"})),
            "shed": int(_c_srv_req.value({"event": "shed"})),
            "deadline_expired": int(_c_srv_req.value(
                {"event": "deadline"})),
            "cancelled": int(_c_srv_req.value({"event": "cancelled"})),
            "ttft_p50_s": round(_h_srv_ttft.percentile(50), 6),
            "ttft_p99_s": round(_h_srv_ttft.percentile(99), 6),
            "tpot_p50_s": round(_h_srv_tpot.percentile(50), 6),
            "tpot_p99_s": round(_h_srv_tpot.percentile(99), 6),
            "queue_depth": int(_g_srv_queue.value()),
            "running": int(_g_srv_running.value()),
            "kv_block_utilization": round(float(_g_srv_util.value()), 4),
            "steps_total": int(_c_srv_steps.value()),
            "step_builds": int(_c_srv_builds.value()),
            "prefix_cached_tokens": int(_c_srv_prefix.value()),
            "cow_copies": int(_c_srv_cow.value()),
            "pallas_steps": int(_c_srv_pallas.value(
                {"kind": "decode"}) + _c_srv_pallas.value(
                {"kind": "mixed"})),
            "ffn_steps": int(_c_ffn.value(
                {"kind": "serving"}) + _c_ffn.value(
                {"kind": "fused_tick"})),
            "fused_ticks": int(_c_ffn.value({"kind": "fused_tick"})),
            "ffn_fallbacks": int(sum(_c_ffn_fb.value({"reason": r})
                                     for r in ("unavailable", "unsupported",
                                               "quant"))),
            "kv_bytes_in_use": int(_g_srv_bytes.value()),
            "kv_bytes_total": int(_g_srv_bytes_total.value()),
        },
        "quant": {
            "calibration_runs": int(_c_q_calib.value()),
            "quantized_matmuls": int(_c_q_mm.value()),
            "kv_quant_tokens": int(_c_q_kv_q.value()),
            "kv_dequant_pages": int(_c_q_kv_dq.value()),
            "manifest_loads_ok": int(_c_q_manifest.value(
                {"result": "ok"})),
        },
        "pipeline": {
            "runs": int(_c_pp_runs.value()),
            "sends": int(_c_pp_sends.value()),
            "recvs": int(_c_pp_recvs.value()),
            "stalls": int(_c_pp_stalls.value()),
            "stage_builds": int(_c_pp_builds.value()),
            "p2p_transfers": int(_c_p2p.value()),
            "bubble_fraction": round(float(_g_pp_bubble.value()), 6),
            "measured_bubble_fraction": round(
                float(_g_pp_mbubble.value()), 6),
            "bubble_gap": round(float(_g_pp_bgap.value()), 6),
            "straggler_group": int(_g_pp_strag.value()),
            "straggler_excess": round(float(_g_pp_strag_x.value()), 4),
            "stage_skew": round(float(_g_pp_skew.value()), 4),
            "send_p50_s": round(_h_pp_send.percentile(50), 6),
            "send_p99_s": round(_h_pp_send.percentile(99), 6),
            "wire_bytes": int(_c_pp_wire.value()),
            "wire_bytes_ref": int(_c_pp_wire_ref.value()),
            "wire_compression_ratio": _ratio(
                _c_pp_wire_ref.value(), _c_pp_wire.value()),
        },
        "router": {
            "admitted": int(_c_rt_admit.value()),
            "completed": int(_c_rt_complete.value()),
            "shed": int(_c_rt_shed.value()),
            "assignments": int(_c_rt_assign.value()),
            "prefix_routed": int(_c_rt_prefix.value()),
            "failovers": int(_c_rt_failover.value()),
            "failover_mismatches": int(_c_rt_mismatch.value()),
            "migrations": int(_c_rt_migrate.value()),
            "readmits": int(_c_rt_readmit.value()),
            "drains": int(_c_rt_drain.value()),
            "pending": int(_g_rt_pending.value()),
            "live_streams": int(_g_rt_live.value()),
            "replicas": {
                s: int(_g_rt_replicas.value({"state": s}))
                for s in ("healthy", "degraded", "dead", "draining",
                          "drained")},
            # fleet-aggregate SLOs: every replica engine feeds the same
            # process-wide serving histograms, so these ARE the
            # cross-replica percentiles
            "ttft_p50_s": round(_h_srv_ttft.percentile(50), 6),
            "ttft_p99_s": round(_h_srv_ttft.percentile(99), 6),
            "tpot_p50_s": round(_h_srv_tpot.percentile(50), 6),
            "tpot_p99_s": round(_h_srv_tpot.percentile(99), 6),
        },
        "disagg": {
            "handoffs_ok": int(_c_mig_handoffs.value({"result": "ok"})),
            "handoffs_local": int(_c_mig_handoffs.value(
                {"result": "local"})),
            "handoffs_fallback": int(_c_mig_handoffs.value(
                {"result": "fallback"})),
            "pages_shipped": int(_c_mig_pages.value()),
            "wire_bytes": int(_c_mig_bytes.value()),
            "pull_retries": int(_c_mig_retries.value()),
            "recompute_fallbacks": int(_c_mig_fallbacks.value()),
            "monolithic_trips": int(_c_mig_mono.value()),
            "autoscaler_grows": int(_c_as_decisions.value(
                {"direction": "grow"})),
            "autoscaler_shrinks": int(_c_as_decisions.value(
                {"direction": "shrink"})),
            "decode_pool": int(_g_as_pool.value()),
        },
        "adapters": {
            "registered": int(_c_ad_reg.value()),
            "loads": int(_c_ad_loads.value()),
            "swaps": int(_c_ad_swaps.value()),
            "evictions": int(_c_ad_evict.value()),
            "hits": int(_c_ad_hits.value()),
            "resident": int(_g_ad_resident.value()),
            "bytes_in_use": int(_g_ad_bytes.value()),
            "bytes_total": int(_g_ad_bytes_total.value()),
            "manifest_loads_ok": int(_c_ad_manifest.value(
                {"result": "ok"})),
            "prefetches_ok": int(_c_ad_prefetch.value({"result": "ok"})),
            "prefetch_misses": int(_c_ad_prefetch.value(
                {"result": "miss"}) + _c_ad_prefetch.value(
                {"result": "corrupt"})),
        },
        "spec": {
            "ticks": int(_c_spec_ticks.value()),
            "proposed": int(_c_spec_prop.value()),
            "accepted": int(_c_spec_acc.value()),
            "bonus": int(_c_spec_bonus.value()),
            "draft_steps": int(_c_spec_draft.value()),
            "acceptance_rate": round(float(_g_spec_rate.value()), 4),
        },
        "tuner": {
            "candidates_enumerated": int(_c_tune_cand.value(
                {"outcome": "enumerated"})),
            "candidates_pruned": int(_c_tune_cand.value(
                {"outcome": "pruned"})),
            "candidates_measured": int(_c_tune_cand.value(
                {"outcome": "measured"})),
            "predicted_step_s": round(float(_g_tune_pred.value()), 6),
            "measured_step_s": round(float(_g_tune_meas.value()), 6),
            "gap_ratio": round(float(_g_tune_gap.value()), 4),
            "profile_loads_ok": int(_c_tune_profile.value(
                {"result": "ok"})),
            "profiles_applied": int(_c_tune_profile.value(
                {"result": "applied"})),
        },
    }


def fleet_summary(store=None, ranks=None, states=None) -> dict:
    """Fleet-global SLO digest (merged TTFT/TPOT percentiles, shed rate);
    see fleet.py. With no store: the local registry as a fleet of one."""
    from . import fleet

    return fleet.fleet_summary(store=store, ranks=ranks, states=states)


def reset():
    """Zero every metric and clear the ring (bench/test isolation)."""
    _registry.reset()
    _recorder.clear()
    tracing.reset()


def dump_distress(reason: str, extra: dict = None,
                  directory: str = None) -> str:
    from . import distress

    return distress.dump(reason, extra=extra, directory=directory)


def register_distress_section(name: str, fn) -> None:
    """Register fn() -> json-serializable as an extra section of every
    distress dump (e.g. the serving router snapshots its fleet state
    into post-mortems). fn=None unregisters."""
    from . import distress

    distress.register_section(name, fn)


def install_signal_handler() -> bool:
    from . import distress

    return distress.install_signal_handler()


# enforce's distress hook is injected here (not imported by enforce) so
# core/enforce.py keeps zero observability dependencies
from . import distress as _distress  # noqa: E402

_distress.install_enforce_hook()

# span plane last (it emits through the choke point above); registers the
# in-flight span tree as the distress "traces" section
from . import tracing  # noqa: E402

tracing.install()
