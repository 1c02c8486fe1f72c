"""Bench-record contract: schema validation + damaged-record recovery.

scripts/bench_schema.py guards the record bench.py emits (BENCH_OUT.json
+ final stdout line); scripts/gen_perf_tables.py must recover a record
from a driver wrapper whose ``parsed`` is null — and fail loudly when
the stdout tail was truncated mid-object (BENCH_r05's actual damage)."""

import importlib.util
import json
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load(name):
    path = REPO / "scripts" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def schema():
    return _load("bench_schema")


@pytest.fixture(scope="module")
def tables():
    return _load("gen_perf_tables")


def _rung(rate, completion=1.0, p50=50.0, p95=80.0):
    return {"offered_req_s": rate, "req_per_s": rate,
            "completion": completion, "decode_tokens_per_s": rate * 32,
            "ttft_p50_ms": p50, "ttft_p95_ms": p95}


def _serving(knee=2.0, saturated=False):
    head = ({k: None for k in ("arrival_rate_req_s", "req_per_s",
                               "decode_tokens_per_s", "ttft_p50_ms",
                               "ttft_p95_ms")}
            if saturated else
            {"arrival_rate_req_s": knee, "req_per_s": knee,
             "decode_tokens_per_s": knee * 32, "ttft_p50_ms": 50.0,
             "ttft_p95_ms": 80.0})
    return dict(head, ladder=[_rung(1.0), _rung(2.0)],
                knee_req_s=None if saturated else knee,
                saturated=saturated, burst_req_per_s=9.0,
                burst_decode_tokens_per_s=288.0, prompt_len=128,
                gen=32, slots=48, kv="int8", decode_kernel="fused")


def _record(**serving_kw):
    return {"metric": "llama_319M_train_tokens_per_sec_per_chip",
            "value": 1234.5, "unit": "tokens/sec/chip",
            "extra": {"serving": _serving(**serving_kw)}}


def _mix_block(**serving_kw):
    d = _serving(**serving_kw)
    d["batching"] = "ragged"
    d["prompt_mix"] = {"name": "short_chat", "lens": [32, 64, 128],
                       "weights": [0.5, 0.3, 0.2], "sampled_p50": 32,
                       "sampled_p95": 128, "sampled_max": 128}
    return d


def _mixed_record(**serving_kw):
    rec = _record()
    rec["extra"]["serving_mixed"] = {
        "batching": "ragged",
        "mixes": {"short_chat": _mix_block(**serving_kw),
                  "long_rag": _mix_block(saturated=True)}}
    return rec


def test_valid_record_is_clean(schema):
    assert schema.validate_record(_record()) == []


def test_valid_saturated_record_is_clean(schema):
    assert schema.validate_record(_record(saturated=True)) == []


def test_missing_top_level_keys(schema):
    rec = _record()
    del rec["metric"]
    rec["value"] = "fast"
    probs = schema.validate_record(rec)
    assert any("metric" in p for p in probs)
    assert any("value" in p for p in probs)


def test_knee_and_saturated_are_exclusive(schema):
    rec = _record()
    rec["extra"]["serving"]["saturated"] = True  # but knee_req_s set
    probs = schema.validate_record(rec)
    assert any("not both" in p for p in probs)

    rec = _record(saturated=True)
    rec["extra"]["serving"]["saturated"] = False  # but knee is null
    probs = schema.validate_record(rec)
    assert any("must name its knee" in p for p in probs)


def test_saturated_record_may_not_carry_headline_numbers(schema):
    rec = _record(saturated=True)
    rec["extra"]["serving"]["ttft_p50_ms"] = 247.1
    probs = schema.validate_record(rec)
    assert any("headline" in p for p in probs)


def test_ladder_rungs_must_be_numeric(schema):
    rec = _record()
    rec["extra"]["serving"]["ladder"][1]["completion"] = None
    probs = schema.validate_record(rec)
    assert any("ladder[1].completion" in p for p in probs)


def test_error_leg_is_valid(schema):
    rec = _record()
    rec["extra"]["serving_1b"] = {"error": "RESOURCE_EXHAUSTED"}
    assert schema.validate_record(rec) == []


# --- mixed-length ladder blocks --------------------------------------------


def test_valid_mixed_record_is_clean(schema):
    assert schema.validate_record(_mixed_record()) == []


def test_mixed_knee_saturated_exclusivity_applies_per_mix(schema):
    rec = _mixed_record()
    mix = rec["extra"]["serving_mixed"]["mixes"]["long_rag"]
    mix["knee_req_s"] = 2.0  # but the mix says saturated
    probs = schema.validate_record(rec)
    assert any("mixes[long_rag]" in p and "not both" in p for p in probs)


def test_mix_without_prompt_distribution_is_flagged(schema):
    rec = _mixed_record()
    del rec["extra"]["serving_mixed"]["mixes"]["short_chat"]["prompt_mix"]
    probs = schema.validate_record(rec)
    assert any("missing prompt_mix" in p for p in probs)


def test_prompt_mix_weights_must_sum_to_one_over_lens(schema):
    rec = _mixed_record()
    pm = rec["extra"]["serving_mixed"]["mixes"]["short_chat"]["prompt_mix"]
    pm["weights"] = [0.5, 0.3]  # length mismatch
    probs = schema.validate_record(rec)
    assert any("3 lens but 2 weights" in p for p in probs)
    pm["weights"] = [0.5, 0.3, 0.1]  # sums to 0.9
    probs = schema.validate_record(rec)
    assert any("sum to 0.9" in p for p in probs)
    pm["weights"] = [0.5, 0.3, "lots"]
    probs = schema.validate_record(rec)
    assert any("non-negative numbers" in p for p in probs)


def test_mixed_block_requires_batching_and_mixes(schema):
    rec = _mixed_record()
    rec["extra"]["serving_mixed"]["batching"] = "eager"
    rec["extra"]["serving_mixed"]["mixes"] = {}
    probs = schema.validate_record(rec)
    assert any("batching" in p for p in probs)
    assert any("non-empty object" in p for p in probs)


def _prefix_block():
    return {"requests": 96, "hit_ratio": 0.61, "hit_token_ratio": 0.45,
            "cold_requests": 30, "hit50_requests": 40,
            "ttft_mean_cold_ms": 82.0, "ttft_mean_hit50_ms": 31.0,
            "ttft_p50_cold_ms": 78.0, "ttft_p50_hit50_ms": 29.0,
            "cached_pages": 120, "evicted_pages": 14}


def test_prefix_block_valid(schema):
    rec = _mixed_record()
    mixes = rec["extra"]["serving_mixed"]["mixes"]
    mixes["zipf_chat"] = _mix_block()
    mixes["zipf_chat"]["prefix"] = _prefix_block()
    assert schema.validate_record(rec) == []


def test_prefix_block_ratio_bounds_and_required_keys(schema):
    rec = _mixed_record()
    mixes = rec["extra"]["serving_mixed"]["mixes"]
    mixes["zipf_chat"] = _mix_block()
    px = _prefix_block()
    px["hit_ratio"] = 1.4
    del px["cached_pages"]
    mixes["zipf_chat"]["prefix"] = px
    probs = schema.validate_record(rec)
    assert any("hit_ratio=1.4" in p and "outside [0, 1]" in p
               for p in probs)
    assert any("prefix.cached_pages" in p for p in probs)


def test_prefix_block_ttft_null_only_when_class_empty(schema):
    """A cold TTFT may be null ONLY when there were no cold requests —
    otherwise a run could fake an unbeatable cache by dropping its
    baseline."""
    rec = _mixed_record()
    mixes = rec["extra"]["serving_mixed"]["mixes"]
    mixes["zipf_chat"] = _mix_block()
    px = _prefix_block()
    px["ttft_mean_cold_ms"] = None  # but cold_requests = 30
    mixes["zipf_chat"]["prefix"] = px
    probs = schema.validate_record(rec)
    assert any("null" in p and "ttft_mean_cold_ms" in p for p in probs)
    px["cold_requests"] = 0  # empty class: null is now honest
    assert schema.validate_record(rec) == []
    px["ttft_mean_hit50_ms"] = "fast"
    probs = schema.validate_record(rec)
    assert any("ttft_mean_hit50_ms" in p and "neither" in p
               for p in probs)


def test_mixed_error_leg_is_valid(schema):
    rec = _record()
    rec["extra"]["serving_1b_mixed"] = {"error": "RESOURCE_EXHAUSTED"}
    assert schema.validate_record(rec) == []
    rec["extra"]["serving_1b_mixed"] = {
        "batching": "ragged",
        "mixes": {"bursty": {"error": "RESOURCE_EXHAUSTED"}}}
    assert schema.validate_record(rec) == []


# --- speculative-decoding blocks -------------------------------------------


def _spec_block():
    return {"rounds": 40, "drafted_tokens": 160, "accepted_tokens": 150,
            "accept_ratio": 0.938, "accepted_tokens_per_step": 4.75,
            "cooldowns": 0, "k": 4, "draft": "self"}


def _spec_ablation_block():
    return {"on": {"decode_tokens_per_s": 520.0, "accept_ratio": 0.94,
                   "accepted_tokens_per_step": 4.75},
            "off": {"decode_tokens_per_s": 310.0},
            "speedup": 1.68}


def test_spec_blocks_valid(schema):
    rec = _mixed_record()
    mix = rec["extra"]["serving_mixed"]["mixes"]["short_chat"]
    mix["spec"] = _spec_block()
    mix["spec_ablation"] = _spec_ablation_block()
    assert schema.validate_record(rec) == []
    # A standalone serving leg may carry spec without the ablation.
    rec2 = _record()
    rec2["extra"]["serving"]["spec"] = _spec_block()
    assert schema.validate_record(rec2) == []
    # An honest probe error passes through.
    mix["spec_ablation"] = {"error": "RESOURCE_EXHAUSTED"}
    assert schema.validate_record(rec) == []


def test_spec_block_absent_not_zero(schema):
    """A leg that never completed a verify round must omit the spec
    block entirely — rounds=0 inside one is flagged."""
    rec = _record()
    sp = _spec_block()
    sp["rounds"] = 0
    rec["extra"]["serving"]["spec"] = sp
    probs = schema.validate_record(rec)
    assert any("absent, not zero" in p for p in probs)


def test_spec_ratio_bounds_and_accept_le_drafted(schema):
    rec = _record()
    sp = _spec_block()
    sp["accept_ratio"] = 1.4
    sp["accepted_tokens"] = 200  # > drafted 160
    rec["extra"]["serving"]["spec"] = sp
    probs = schema.validate_record(rec)
    assert any("accept_ratio=1.4" in p and "[0, 1]" in p for p in probs)
    assert any("accepts a prefix of its draft" in p for p in probs)
    sp = _spec_block()
    sp["accept_ratio"] = None  # but drafted_tokens = 160
    rec["extra"]["serving"]["spec"] = sp
    probs = schema.validate_record(rec)
    assert any("null is only honest" in p for p in probs)


def test_spec_tokens_per_step_must_be_positive(schema):
    rec = _record()
    sp = _spec_block()
    sp["accepted_tokens_per_step"] = 0
    rec["extra"]["serving"]["spec"] = sp
    probs = schema.validate_record(rec)
    assert any("accepted_tokens_per_step" in p and "bonus token" in p
               for p in probs)


def test_spec_ablation_iff_spec_ran(schema):
    """A speculative MIX leg must carry its on/off A/B, and no leg may
    carry an ablation without a spec block."""
    rec = _mixed_record()
    mix = rec["extra"]["serving_mixed"]["mixes"]["short_chat"]
    mix["spec"] = _spec_block()  # no spec_ablation
    probs = schema.validate_record(rec)
    assert any("must carry its on/off A/B" in p for p in probs)
    del mix["spec"]
    mix["spec_ablation"] = _spec_ablation_block()
    probs = schema.validate_record(rec)
    assert any("a leg that never speculated" in p for p in probs)


def test_spec_ablation_leg_shapes(schema):
    rec = _mixed_record()
    mix = rec["extra"]["serving_mixed"]["mixes"]["short_chat"]
    mix["spec"] = _spec_block()
    ab = _spec_ablation_block()
    ab["off"]["accept_ratio"] = 0.9  # off leg has no acceptance
    del ab["on"]["decode_tokens_per_s"]
    mix["spec_ablation"] = ab
    probs = schema.validate_record(rec)
    assert any("spec-off leg has no acceptance" in p for p in probs)
    assert any("on.decode_tokens_per_s" in p for p in probs)


def _multihost_rung(shards=2, tp=2, mode="int8", dcn=1152,
                    ratio=3.55):
    return {"shards": shards, "tp": tp, "dcn_collective": mode,
            "toks_per_s": 120.0, "ici_bytes_per_step": 4096,
            "dcn_bytes_per_step": dcn,
            "dcn_bytes_ratio_vs_fp32": ratio}


def _multihost_block():
    return {"ladder": [
        _multihost_rung(shards=1, tp=4, mode="bf16", dcn=0, ratio=None),
        _multihost_rung(mode="bf16", dcn=4096, ratio=1.0),
        _multihost_rung(mode="int8"),
    ]}


def test_multihost_block_valid(schema):
    rec = _record()
    rec["extra"]["serving_multihost"] = _multihost_block()
    assert schema.validate_record(rec) == []
    rec["extra"]["serving_multihost"] = {"error": "RESOURCE_EXHAUSTED"}
    assert schema.validate_record(rec) == []


def test_multihost_rung_required_keys_and_bounds(schema):
    rec = _record()
    mh = _multihost_block()
    del mh["ladder"][2]["dcn_bytes_per_step"]
    mh["ladder"][1]["toks_per_s"] = 0
    rec["extra"]["serving_multihost"] = mh
    probs = schema.validate_record(rec)
    assert any("dcn_bytes_per_step" in p for p in probs)
    assert any("toks_per_s" in p for p in probs)


def test_multihost_int8_rung_must_show_3x(schema):
    """The quantization claim is load-bearing: an int8 rung whose
    recorded ratio is under 3x (or missing) fails validation."""
    rec = _record()
    mh = _multihost_block()
    mh["ladder"][2]["dcn_bytes_ratio_vs_fp32"] = 2.3
    rec["extra"]["serving_multihost"] = mh
    assert any(">= 3x" in p for p in schema.validate_record(rec))
    mh["ladder"][2]["dcn_bytes_ratio_vs_fp32"] = None
    assert any(">= 3x" in p for p in schema.validate_record(rec))


def test_multihost_multi_shard_rungs_need_ablation(schema):
    """Multi-shard rungs with only one DCN mode recorded — the
    quantized-vs-exact ablation never ran — are flagged."""
    rec = _record()
    mh = _multihost_block()
    mh["ladder"] = [r for r in mh["ladder"]
                    if r["dcn_collective"] == "int8" or r["shards"] == 1]
    rec["extra"]["serving_multihost"] = mh
    assert any("ablation" in p for p in schema.validate_record(rec))


def test_multihost_multi_shard_rung_puts_bytes_on_dcn(schema):
    rec = _record()
    mh = _multihost_block()
    mh["ladder"][2]["dcn_bytes_per_step"] = 0
    rec["extra"]["serving_multihost"] = mh
    probs = schema.validate_record(rec)
    assert any("puts bytes on the DCN" in p for p in probs)


# --- disaggregated prefill/decode ablation ---------------------------------


def _disagg_leg():
    return {"ttft_p50_ms": 120.0, "ttft_p95_ms": 310.0,
            "itl_p50_ms": 18.0, "itl_p95_ms": 42.0,
            "decode_tokens_per_s": 410.0}


def _disagg_block():
    dis = dict(_disagg_leg(), handoff_gap_p50_ms=12.0,
               migration={"pages": 84, "wire_bytes": 1376256,
                          "seconds": 0.41, "failed": 0})
    return {"mix": {"name": "long_rag", "lens": [512, 1024, 1536],
                    "weights": [0.3, 0.5, 0.2]},
            "n_requests": 10, "gen": 24, "handoff_after_tokens": 2,
            "transfer": "int8", "unified": _disagg_leg(),
            "disagg": dis, "itl_p95_ratio": 1.35}


def test_disagg_block_valid(schema):
    rec = _record()
    rec["extra"]["serving_disagg"] = _disagg_block()
    assert schema.validate_record(rec) == []
    rec["extra"]["serving_disagg"] = {"error": "RESOURCE_EXHAUSTED"}
    assert schema.validate_record(rec) == []


def test_disagg_required_keys_and_legs(schema):
    rec = _record()
    blk = _disagg_block()
    del blk["unified"]["itl_p95_ms"]
    blk["transfer"] = "bf16"
    rec["extra"]["serving_disagg"] = blk
    probs = schema.validate_record(rec)
    assert any("unified.itl_p95_ms" in p for p in probs)
    assert any("transfer must be 'int8' or 'exact'" in p for p in probs)


def test_disagg_leg_must_move_pages(schema):
    """A 'disagg' ablation whose migration block shows no pages on the
    wire never disaggregated anything — flagged, as are pages without
    bytes and a missing migration block entirely."""
    rec = _record()
    blk = _disagg_block()
    rec["extra"]["serving_disagg"] = blk
    blk["disagg"]["migration"]["pages"] = 0
    probs = schema.validate_record(rec)
    assert any("measured unified serving twice" in p for p in probs)
    blk["disagg"]["migration"] = {"pages": 5, "wire_bytes": 0,
                                  "seconds": 0.1, "failed": 0}
    probs = schema.validate_record(rec)
    assert any("no bytes on the wire" in p for p in probs)
    del blk["disagg"]["migration"]
    probs = schema.validate_record(rec)
    assert any("missing migration block" in p for p in probs)


def test_disagg_mix_distribution_checked(schema):
    rec = _record()
    blk = _disagg_block()
    blk["mix"]["weights"] = [0.3, 0.5]
    rec["extra"]["serving_disagg"] = blk
    assert any("3 lens but 2 weights" in p
               for p in schema.validate_record(rec))
    blk["mix"]["weights"] = [0.3, 0.5, 0.1]
    assert any("sum to 0.9" in p for p in schema.validate_record(rec))


# --- LoRA multiplexing ablation --------------------------------------------


def _adapters_block():
    return {"mix": {"name": "zipf_adapters", "n_adapters": 6,
                    "zipf_alpha": 1.1, "pool_adapters": 4, "rank": 4},
            "n_requests": 24, "gen": 16,
            "single_model": {"tokens_per_s": 420.0,
                             "ttft_p50_ms": 35.0, "ttft_p95_ms": 80.0},
            "multi": {"tokens_per_s": 365.0, "ttft_p50_ms": 41.0,
                      "ttft_p95_ms": 96.0,
                      "pool": {"pool_pages": 8, "resident": 4,
                               "hits": 17, "misses": 7,
                               "evictions": 3, "hit_ratio": 0.708}},
            "throughput_degradation": 0.869}


def test_adapters_block_valid(schema):
    rec = _record()
    rec["extra"]["serving_adapters"] = _adapters_block()
    assert schema.validate_record(rec) == []
    rec["extra"]["serving_adapters"] = {"error": "RESOURCE_EXHAUSTED"}
    assert schema.validate_record(rec) == []


def test_adapters_hit_ratio_is_a_fraction(schema):
    rec = _record()
    blk = _adapters_block()
    rec["extra"]["serving_adapters"] = blk
    blk["multi"]["pool"]["hit_ratio"] = 1.7
    probs = schema.validate_record(rec)
    assert any("hit_ratio" in p and "[0, 1]" in p for p in probs)
    del blk["multi"]["pool"]
    probs = schema.validate_record(rec)
    assert any("missing pool block" in p for p in probs)


def test_adapters_degradation_iff_both_legs_ran(schema):
    """throughput_degradation must exist when both legs ran and must
    NOT exist when one didn't — a ratio over a missing leg is
    fabricated."""
    rec = _record()
    blk = _adapters_block()
    rec["extra"]["serving_adapters"] = blk
    blk["throughput_degradation"] = None
    probs = schema.validate_record(rec)
    assert any("never priced the multiplexing" in p for p in probs)
    blk = _adapters_block()
    del blk["single_model"]
    rec["extra"]["serving_adapters"] = blk
    probs = schema.validate_record(rec)
    assert any("a ratio over a leg that never ran" in p for p in probs)


def test_prefix_migration_cost_field(schema):
    """The migrated-vs-recomputed field in the zipf_chat prefix block:
    valid when complete, per-page cost null only when nothing moved,
    and an honest probe error passes through."""
    rec = _mixed_record()
    mixes = rec["extra"]["serving_mixed"]["mixes"]
    mixes["zipf_chat"] = _mix_block()
    px = _prefix_block()
    px["migration"] = {"migrated_pages": 120, "wire_bytes": 1966080,
                       "seconds": 0.8, "migrate_s_per_page": 0.0067,
                       "recompute_s_per_page": 0.021,
                       "migrate_vs_recompute": 3.13}
    mixes["zipf_chat"]["prefix"] = px
    assert schema.validate_record(rec) == []
    px["migration"]["migrate_s_per_page"] = None  # but pages moved
    probs = schema.validate_record(rec)
    assert any("null migrate_s_per_page" in p for p in probs)
    px["migration"] = {"migrated_pages": 120, "wire_bytes": 0,
                       "seconds": 0.8, "migrate_s_per_page": 0.0067}
    probs = schema.validate_record(rec)
    assert any("no bytes on the wire" in p for p in probs)
    px["migration"] = {"error": "cold engine OOM"}
    assert schema.validate_record(rec) == []


# --- autoscaling chaos leg --------------------------------------------------


def _chaos_block():
    return {"mix": "zipf_chat", "offered": 24, "completed": 21,
            "shed": 3, "failed": 0, "shed_fraction": 0.125,
            "goodput_ratio": 1.0, "scale_ups": 1, "scale_downs": 1,
            "drain_retirements": 2, "kills": 1,
            "controller_kills": 1, "recovery_seconds": 1.42,
            "max_groups": 3, "max_replicas": 3, "gen": 10,
            "doctor": {"checks_run": 14, "violations": 0,
                       "audit_seconds": 0.02}}


def test_chaos_block_valid(schema):
    rec = _record()
    rec["extra"]["serving_chaos"] = _chaos_block()
    assert schema.validate_record(rec) == []
    rec["extra"]["serving_chaos"] = {"error": "RESOURCE_EXHAUSTED"}
    assert schema.validate_record(rec) == []


def test_chaos_required_keys_and_fractions(schema):
    rec = _record()
    blk = _chaos_block()
    del blk["kills"]
    blk["goodput_ratio"] = 1.3
    rec["extra"]["serving_chaos"] = blk
    probs = schema.validate_record(rec)
    assert any("missing required key 'kills'" in p for p in probs)
    assert any("goodput_ratio=1.3" in p and "[0, 1]" in p for p in probs)


def test_chaos_leg_must_exercise_the_policy(schema):
    """A chaos record showing no scale-up, no scale-down, or no kill
    measured a static fleet on a sunny day — each is flagged."""
    rec = _record()
    blk = _chaos_block()
    blk["scale_ups"] = 0
    blk["scale_downs"] = 0
    blk["kills"] = 0
    rec["extra"]["serving_chaos"] = blk
    probs = schema.validate_record(rec)
    assert any("scale_ups=0" in p and "static fleet" in p for p in probs)
    assert any("scale_downs=0" in p and "drain" in p for p in probs)
    assert any("kills=0" in p for p in probs)


def test_chaos_doctor_requires_clean_audit(schema):
    """The post-ramp doctor audit gates the record: any violation, or
    a pass that ran zero checks, flags the leg no matter how healthy
    its goodput looks.  A legacy block without a doctor key stays
    valid (old records predate the audit plane)."""
    rec = _record()
    blk = _chaos_block()
    blk["doctor"] = {"checks_run": 0, "violations": 2,
                     "audit_seconds": -1.0}
    rec["extra"]["serving_chaos"] = blk
    probs = schema.validate_record(rec)
    assert any("checks_run=0" in p and "audited" in p for p in probs)
    assert any("violations=2" in p and "corrupted" in p for p in probs)
    assert any("audit_seconds=-1.0" in p for p in probs)
    blk["doctor"] = "clean"
    assert any("not an object" in p
               for p in schema.validate_record(rec))
    del blk["doctor"]
    assert schema.validate_record(rec) == []


def test_chaos_sheds_are_not_completions(schema):
    """completed + shed must not exceed offered: a leg double-counting
    shed requests as completions is cooking its goodput."""
    rec = _record()
    blk = _chaos_block()
    blk["completed"] = 23  # 23 + 3 > 24 offered
    rec["extra"]["serving_chaos"] = blk
    probs = schema.validate_record(rec)
    assert any("exceeds offered=24" in p for p in probs)


def test_chaos_scale_up_reasons_breakdown(schema):
    """ISSUE 18 satellite: the scale_up_reasons breakdown uses known
    reasons only, counts >= 1 (absent-not-zero — a reason that never
    fired is omitted, never reported as 0), and sums to scale_ups."""
    rec = _record()
    blk = _chaos_block()
    blk["scale_ups"] = 3
    blk["scale_up_reasons"] = {"arrival_slope": 1, "queue_age": 2}
    rec["extra"]["serving_chaos"] = blk
    assert schema.validate_record(rec) == []

    blk["scale_up_reasons"] = {"vibes": 3}
    probs = schema.validate_record(rec)
    assert any("unknown reason 'vibes'" in p for p in probs)

    blk["scale_up_reasons"] = {"arrival_slope": 0, "queue_age": 3}
    probs = schema.validate_record(rec)
    assert any("arrival_slope=0" in p and "omitted, not zero" in p
               for p in probs)

    blk["scale_up_reasons"] = {"queue_age": 1}  # sums to 1, not 3
    probs = schema.validate_record(rec)
    assert any("breakdown sums to 1" in p and "scale_ups=3" in p
               for p in probs)

    # Field absent entirely: valid (older records never measured it).
    del blk["scale_up_reasons"]
    assert schema.validate_record(rec) == []


def test_chaos_controller_kill_requires_measured_recovery(schema):
    """ISSUE 20 satellite: the control-plane chaos arm.  A record
    claiming controller_kills >= 1 must carry a numeric
    recovery_seconds >= 0 (the kill was observed recovering);
    legacy records without either key stay valid, and a kill-free
    record may honestly report recovery_seconds as null."""
    rec = _record()
    blk = _chaos_block()
    rec["extra"]["serving_chaos"] = blk
    assert schema.validate_record(rec) == []

    # Killed the controller but never measured the recovery: invalid.
    blk["recovery_seconds"] = None
    probs = schema.validate_record(rec)
    assert any("controller_kills=1" in p
               and "recovery_seconds=None" in p for p in probs)
    blk["recovery_seconds"] = "fast"
    probs = schema.validate_record(rec)
    assert any("recovery_seconds='fast'" in p for p in probs)

    # No controller kill this run: null recovery is honest.
    blk["controller_kills"] = 0
    blk["recovery_seconds"] = None
    assert schema.validate_record(rec) == []
    blk["recovery_seconds"] = "fast"  # but a non-number still isn't
    probs = schema.validate_record(rec)
    assert any("neither a number nor null" in p for p in probs)

    blk["controller_kills"] = -1
    blk["recovery_seconds"] = None
    probs = schema.validate_record(rec)
    assert any("controller_kills=-1" in p for p in probs)

    # Pre-FT record: both keys absent entirely — valid.
    del blk["controller_kills"]
    del blk["recovery_seconds"]
    assert schema.validate_record(rec) == []


def test_bench_out_if_present(schema):
    """Whatever BENCH_OUT.json the last bench run left behind must
    satisfy the schema (skips when no run has happened here)."""
    path = REPO / "BENCH_OUT.json"
    if not path.exists():
        pytest.skip("no BENCH_OUT.json in the repo")
    rec = json.loads(path.read_text())
    assert schema.validate_record(rec) == []


def _stubbed_bench(monkeypatch):
    """bench.py with every leg stubbed and the device check satisfied:
    what is left is main()'s own record assembly and exit code."""
    import jax

    from ray_tpu.utils import accelerator

    spec = importlib.util.spec_from_file_location("bench",
                                                  REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr(bench, "_require_tpu", jax.devices)
    monkeypatch.setattr(accelerator, "local_chip_spec",
                        lambda: accelerator.chip_spec("TPU v5 lite"))
    monkeypatch.setattr(accelerator, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(bench, "_measure", lambda *a, **k: 1000.0)
    for leg, block in [
            ("_measure_serving", _serving),
            ("_measure_serving_mixed",
             lambda: _mixed_record()["extra"]["serving_mixed"]),
            ("_measure_8b",
             lambda: {"params_b": 8.03,
                      "train": {**_zero_train(), "optimizer": "adamw8bit"}}),
            ("_measure_serving_multihost", _multihost_block),
            ("_measure_serving_disagg", _disagg_block),
            ("_measure_serving_adapters", _adapters_block),
            ("_measure_serving_chaos", _chaos_block)]:
        monkeypatch.setattr(bench, leg,
                            lambda *a, _block=block, **k: _block())
    return bench


def test_bench_main_refuses_to_run_off_a_tpu():
    """No CPU fallback: on this machine (JAX pinned to the CPU) main()
    exits non-zero and names the platform it found."""
    spec = importlib.util.spec_from_file_location("bench",
                                                  REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert "platform='cpu'" in str(exc.value.code)


def test_bench_main_exits_nonzero_when_a_leg_errors(tmp_path, monkeypatch,
                                                    capsys):
    """An errored leg still reports beside the others, and the exit
    code says so."""
    bench = _stubbed_bench(monkeypatch)

    def boom(*a, **k):
        raise RuntimeError("Mosaic refused")

    monkeypatch.setattr(bench, "_measure_8b", boom)
    monkeypatch.chdir(tmp_path)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "Mosaic refused" in rec["extra"]["llama_8b"]["error"]
    assert rec["extra"]["serving"]["knee_req_s"] == 2.0


def test_bench_main_emits_file_and_stdout_line(schema, tmp_path,
                                               monkeypatch, capsys):
    """bench.main() end-to-end (device check and measurements stubbed:
    main() itself refuses to run off a TPU): the record lands in
    BENCH_OUT.json AND as the final stdout line, the two copies are
    byte-identical, the line is COMPACT (the driver wrapper keeps only
    a bounded stdout tail — padding is what truncated BENCH_r05's line
    into parsed:null), and the record satisfies the schema."""
    bench = _stubbed_bench(monkeypatch)
    monkeypatch.chdir(tmp_path)
    bench.main()
    lines = capsys.readouterr().out.strip().splitlines()
    file_text = (tmp_path / "BENCH_OUT.json").read_text().strip()
    assert lines[-1] == file_text
    assert ": " not in lines[-1] and ", " not in lines[-1]
    rec = json.loads(lines[-1])
    assert schema.validate_record(rec) == []


# --- the measured full-8B ZeRO train rung ----------------------------------


def _zero_train(shards=4):
    return {"params_b": 8.03, "measured": True,
            "tokens_per_sec_per_chip": 520.0, "mfu": 0.31,
            "zero_sharding": True, "dp_shards": shards, "grad_accum": 4,
            "batch": 4 * shards, "seq": 2048,
            "optimizer": "adamw8bit (int8 states, ZeRO-sharded)",
            "opt_state_bytes_per_param": 2.03 / shards,
            "opt_state_bytes_per_device": 4_075_000_000 // shards,
            "hbm_peak_gb": 11.2}


def _rec_8b(train):
    rec = _record()
    rec["extra"]["llama_8b"] = {"params_b": 8.03, "train": train}
    return rec


def test_zero_train_rung_valid(schema):
    assert schema.validate_record(_rec_8b(_zero_train())) == []


def test_zero_train_error_rung_valid(schema):
    err = {"error": "full-8B AdamW needs ~51.7 GiB/chip on 1 chip(s)",
           "zero_sharding": True, "dp_shards": 1, "min_chips": 4}
    assert schema.validate_record(_rec_8b(err)) == []
    rec = _record()
    rec["extra"]["llama_8b"] = {"error": "RESOURCE_EXHAUSTED"}
    assert schema.validate_record(rec) == []


def test_extrapolated_8b_train_is_retired(schema):
    """A lingering train_extrapolated block — the pre-ZeRO path that
    modeled 32 layers from a 4-layer run — fails validation outright."""
    rec = _rec_8b(_zero_train())
    rec["extra"]["llama_8b"]["train_extrapolated"] = {
        "extrapolated_mfu": 0.45}
    probs = schema.validate_record(rec)
    assert any("train_extrapolated" in p and "retired" in p
               for p in probs)


def test_llama_8b_without_train_rung_is_flagged(schema):
    rec = _record()
    rec["extra"]["llama_8b"] = {"params_b": 8.03}
    probs = schema.validate_record(rec)
    assert any("missing the measured 'train' rung" in p for p in probs)


def test_zero_train_must_be_measured_and_sharded(schema):
    tr = _zero_train()
    tr["measured"] = False
    probs = schema.validate_record(_rec_8b(tr))
    assert any("measured=False" in p and "retired" in p for p in probs)
    tr = _zero_train()
    tr["zero_sharding"] = False
    probs = schema.validate_record(_rec_8b(tr))
    assert any("zero_sharding=False" in p for p in probs)


def test_zero_train_memory_claim_is_checked(schema):
    """opt_state_bytes_per_param must shrink with dp_shards: a rung
    claiming 4-way sharding while reporting ~2 B/param kept its state
    replicated and fails."""
    tr = _zero_train(shards=4)
    tr["opt_state_bytes_per_param"] = 2.03  # replicated footprint
    probs = schema.validate_record(_rec_8b(tr))
    assert any("exceeds" in p and "2.5/dp_shards" in p for p in probs)
    tr["opt_state_bytes_per_param"] = 0.6  # <= 2.5/4
    assert schema.validate_record(_rec_8b(tr)) == []


def test_zero_train_mfu_bounds(schema):
    tr = _zero_train()
    tr["mfu"] = 1.7
    probs = schema.validate_record(_rec_8b(tr))
    assert any("mfu=1.7" in p for p in probs)
    tr["mfu"] = None
    probs = schema.validate_record(_rec_8b(tr))
    assert any("mfu=None" in p for p in probs)


def test_tables_refuse_extrapolated_8b_record(tables):
    rec = _record()
    rec["extra"]["llama_8b"] = {
        "train_extrapolated": {"extrapolated_mfu": 0.45}}
    with pytest.raises(SystemExit, match="retired"):
        tables.render(rec)


def test_tables_refuse_8b_record_without_train_rung(tables):
    rec = _record()
    rec["extra"]["llama_8b"] = {"params_b": 8.03}
    with pytest.raises(SystemExit, match="no measured 'train' rung"):
        tables.render(rec)


def test_tables_render_measured_8b_train_row(tables):
    block = tables.render(_rec_8b(_zero_train()))
    row = next(l for l in block.splitlines()
               if "Llama-3-8B" in l and "MEASURED" in l)
    assert "ZeRO-sharded 4x" in row
    assert "0.5k" in row and "0.31" in row


def test_tables_render_infeasible_8b_train_row(tables):
    """An honest infeasibility record (too few chips even sharded)
    renders an empty row that says why, instead of vanishing."""
    block = tables.render(_rec_8b(
        {"error": "needs ~51.7 GiB/chip", "zero_sharding": True}))
    row = next(l for l in block.splitlines() if "Llama-3-8B" in l)
    assert "infeasible" in row and "| — | — |" in row


# --- gen_perf_tables damaged-record recovery -------------------------------


def test_recover_last_json_line(tables):
    rec = _record()
    wrapper = {"n": 6, "cmd": "python bench.py", "rc": 0, "parsed": None,
               "tail": ("some warning line\n"
                        '{"not": "the record"}\n'
                        + json.dumps(rec) + "\n")}
    got = tables.recover_record(wrapper)
    assert got == rec


def test_recovery_fails_loudly_on_truncated_tail(tables):
    """BENCH_r05's damage: the tail starts mid-object, so no complete
    JSON line survives — the script must die loudly, not guess."""
    wrapper = {"parsed": None,
               "tail": '_s": 21.64, "completion": 0.985}}}'}
    with pytest.raises(SystemExit, match="no complete bench JSON"):
        tables.recover_record(wrapper)


def test_recovery_fails_loudly_on_real_r05_wrapper(tables):
    wrapper = json.loads((REPO / "BENCH_r05.json").read_text())
    assert wrapper["parsed"] is None
    with pytest.raises(SystemExit):
        tables.recover_record(wrapper, "BENCH_r05.json")


def test_render_saturated_ladder_never_shows_a_knee(tables):
    """Old records (no ``saturated`` key) with a collapsed ladder must
    render as saturated, not present the lowest rung as the knee —
    the exact mislabeling BENCH_r05's 1.14B row shipped with."""
    legacy = {"burst_req_per_s": 5.0, "burst_decode_tokens_per_s": 160.0,
              "slots": 32, "kv": "bf16", "knee_req_s": 3.0,
              "arrival_rate_req_s": 3.0, "ttft_p50_ms": 247.1,
              "ttft_p95_ms": 50156.4,
              "ladder": [_rung(3.0, completion=0.116, p50=247.1,
                               p95=50156.4)]}
    rec = {"metric": "m", "value": 1.0, "unit": "u",
           "extra": {"serving_1b": legacy}}
    block = tables.render(rec)
    row = next(l for l in block.splitlines() if "1.14B" in l)
    assert "saturated" in row
    assert "3.0" not in row and "247.1" not in row


def test_render_spec_ablation_table(tables):
    """A mixed record with a speculative mix renders the spec table;
    a record with no spec block anywhere omits it entirely."""
    rec = _mixed_record()
    assert "Speculative decoding" not in tables.render(rec)
    mix = rec["extra"]["serving_mixed"]["mixes"]["short_chat"]
    mix["spec"] = _spec_block()
    mix["spec_ablation"] = _spec_ablation_block()
    block = tables.render(rec)
    assert "Speculative decoding" in block
    row = next(l for l in block.splitlines()
               if l.startswith("| short_chat"))
    assert "0.938" in row and "4.75" in row
    assert "520.0" in row and "310.0" in row and "1.68" in row


def test_render_fused_kernel_row_labeled(tables):
    rec = _record()
    block = tables.render(rec)
    row = next(l for l in block.splitlines()
               if "319M" in l and "slots" in l)
    assert "fused decode" in row
    assert "2.0" in row  # the knee rate
