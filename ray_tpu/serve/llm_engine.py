"""Continuous-batching LLM inference engine for TPU.

The TPU-native heart of the Serve equivalent.  The reference has no
in-tree inference engine (models are user torch code inside replicas;
ray: python/ray/serve/_private/replica.py just invokes the callable) —
on TPU the engine must own the device loop, because XLA wants static
shapes and hates per-request recompiles.  Design:

  * a fixed number of **slots** (the batch dimension of every compiled
    program) over one **paged KV cache**: requests claim a slot and
    pages, block tables map a slot's positions to pages;
  * the **ragged step** (``EngineConfig.ragged_batching``): one jitted
    program a scheduler step, mixing one-token decode rows with prefill
    chunks up to a token budget — the path every benchmark cell
    measures, and the one the prefix cache, LoRA multiplexing,
    speculation and recurrent-state models ride;
  * the **two-program path** (``ragged_batching=False``): bucketed
    prefill and chunked multi-step decode as separate programs — kept
    because it is the only path that runs under a mesh
    (tensor-parallel and multi-host serving);
  * sampling happens **on device** (greedy or temperature), so the only
    per-step host transfer is one int32 per slot;
  * admission interleaves with decode: a new request joins the running
    batch (continuous batching à la Orca; cf. PAPERS.md paged/ragged
    attention).
"""

from __future__ import annotations

import dataclasses
import inspect
import itertools
import logging
import queue
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ray_tpu.util import tracing

_importing = tracing.import_span(__name__)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.core.exceptions import PreemptedError, ShedError  # noqa: E402
from ray_tpu.serve import audit as _audit  # noqa: E402
from ray_tpu.serve import request_events as _reqev  # noqa: E402
from ray_tpu.serve.loop_clock import (  # noqa: E402
    LoopClock,
    LoopSecondsFamily,
)
from ray_tpu.util import xprof  # noqa: E402

xprof.watch_compiles()
_importing.__exit__(None, None, None)

log = logging.getLogger(__name__)

_TELEMETRY = None

# At most one flight-recorder trigger per engine in this many seconds:
# a stalling engine stalls again, and one bundle tells the story.
LOOP_STALL_TRIGGER_INTERVAL_S = 30.0


def _program(name: str, **jit_kwargs):
    """``jax.jit`` of a function renamed to the program's registered
    name with dots as underscores, so that a profiler trace shows the
    module as ``jit_serve_ragged`` whatever the function was called in
    this file and whatever the step holds."""
    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name.replace(".", "_")
        return jax.jit(fn, **jit_kwargs)
    return wrap


def ragged_step_shapes(token_budget: int, max_slots: int) -> Tuple[int, ...]:
    """Lengths of the packed token buffer the engine runs the ragged
    step at, in rising order: ``max_slots`` rounded up to 8, which holds
    every step that carries no more than its decode rows and a short
    prompt tail, and the token budget, which holds every step.  One
    length where rounding leaves no room under the budget."""
    small = -(-max_slots // 8) * 8
    return (small, token_budget) if small < token_budget else (token_budget,)


def _telemetry():
    """Engine metric singletons (created on first engine construction,
    re-registered on later fetches so a test's registry clear() cannot
    silently drop the serving plane from /metrics)."""
    global _TELEMETRY
    from ray_tpu.util import metrics

    if _TELEMETRY is None:
        _TELEMETRY = {
            "ttft": metrics.Histogram(
                "raytpu_serve_ttft_seconds",
                "Time from submit to first generated token, per request.",
                boundaries=[0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                            1.0, 2.5, 5.0, 10.0, 30.0],
            ),
            "tpot": metrics.Histogram(
                "raytpu_serve_tpot_seconds",
                "Mean per-output-token latency after the first token, "
                "per request.",
                boundaries=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                            0.05, 0.1, 0.25, 1.0],
            ),
            "queue_depth": metrics.Gauge(
                "raytpu_serve_queue_depth",
                "Requests admitted nowhere yet: waiting queue + paged "
                "backlog, sampled at dispatch time.",
            ),
            "batch_size": metrics.Histogram(
                "raytpu_serve_decode_batch_size",
                "Active slots per decode dispatch (continuous-batch "
                "occupancy).",
                boundaries=[1, 2, 4, 8, 16, 32, 64],
            ),
            "step_wall": metrics.Gauge(
                "raytpu_serve_step_wall_seconds",
                "High-water mark of the step interval: the time "
                "between consecutive fetched steps while the pipeline "
                "holds work (a chunk's interval over the steps in it). "
                "With the device saturated this is the device's step "
                "time; a stall of host or device widens it.",
            ),
            "loop_seconds": LoopSecondsFamily(),
            "queue_age": metrics.Gauge(
                "raytpu_serve_admission_queue_age_seconds",
                "Age of the oldest request still waiting for admission "
                "(waiting queue + paged backlog), sampled at dispatch "
                "time.  Climbing age with stable depth = stalled "
                "admission, not load.",
            ),
            "itl": metrics.Histogram(
                "raytpu_serve_request_itl_seconds",
                "Worst client-observed inter-token gap within a "
                "finished request (the hiccup a streaming reader "
                "actually sees; mean gap is TPOT).  A speculative "
                "verify round emits several tokens in one burst: the "
                "round's wall gap is divided by the burst size so the "
                "histogram stays an exact per-token partition.",
                boundaries=[0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                            0.1, 0.25, 1.0, 5.0],
            ),
            "spec_rounds": metrics.Counter(
                "raytpu_serve_spec_rounds_total",
                "Speculative verify rounds completed (one draft+verify "
                "cycle of up to spec_k tokens per round).",
            ),
            "spec_drafted": metrics.Counter(
                "raytpu_serve_spec_drafted_tokens_total",
                "Tokens drafted by the draft model across verify "
                "rounds.",
            ),
            "spec_accepted": metrics.Counter(
                "raytpu_serve_spec_accepted_tokens_total",
                "Drafted tokens the target model accepted (the free "
                "bonus token each round emits on top is not counted).",
            ),
            "spec_accept_ratio": metrics.Gauge(
                "raytpu_serve_spec_accept_ratio",
                "Cumulative accepted/drafted token ratio over this "
                "engine's speculative verify rounds.",
            ),
            "slo": metrics.Counter(
                "raytpu_serve_request_slo_total",
                "Terminal requests by SLO outcome: met only when the "
                "request FINISHED inside every bound of "
                "EngineConfig.slo (no slo config = every finish is "
                "met); failed/cancelled always miss.",
                tag_keys=("outcome",),
            ),
            "terminal": metrics.Counter(
                "raytpu_serve_request_terminal_total",
                "Requests reaching a terminal state, by state "
                "(FINISHED / FAILED / CANCELLED / SHED).",
                tag_keys=("state",),
            ),
            "arrived": metrics.Counter(
                "raytpu_serve_requests_arrived_total",
                "Requests submitted to this engine (admitted, shed or "
                "rejected alike) — the raw arrival process.  Its rate "
                "and slope are the LEADING load signal: they move "
                "before the queue forms, which is what predictive "
                "autoscaling (reason arrival_slope) keys on.",
            ),
            "shed": metrics.Counter(
                "raytpu_serve_shed_total",
                "Requests refused at admission because the queue was "
                "already older than the SLO budget "
                "(EngineConfig.shed_queue_age_s) — clean fast-fail "
                "backpressure instead of a guaranteed-late answer.",
            ),
            "goodput": metrics.Gauge(
                "raytpu_serve_goodput_ratio",
                "Tokens from SLO-met requests over all tokens of "
                "terminal requests — goodput vs raw throughput.",
            ),
            "step_tokens": metrics.Counter(
                "raytpu_serve_step_tokens_total",
                "Tokens dispatched to the device, split by phase "
                "(prefill vs decode).  Attributes step wall time: a "
                "rising prefill share explains decode-stream TPOT "
                "regressions without any per-request change.",
                tag_keys=("phase",),
            ),
            "steps": metrics.Counter(
                "raytpu_serve_steps_total",
                "Ragged steps dispatched, by the positions the step's "
                "program was compiled for: the token budget, or "
                "max_slots rounded up to 8 for a step whose tokens fit "
                "there (no prompt chunk to carry).",
                tag_keys=("shape",),
            ),
            "kv_pages_free": metrics.Gauge(
                "raytpu_serve_kv_pages_free",
                "Free pages in the paged KV pool (neither slot-mapped "
                "nor held by the prefix cache).",
            ),
            "kv_pages_cached": metrics.Gauge(
                "raytpu_serve_kv_pages_cached",
                "Pages owned by the prefix cache (0 when the cache is "
                "disabled).  free + cached + slot-owned = pool.",
            ),
            "prefix_requests": metrics.Counter(
                "raytpu_serve_prefix_requests_total",
                "Admitted requests by prefix-cache outcome (hit = at "
                "least one full page reused).",
                tag_keys=("outcome",),
            ),
            "prefix_hit_ratio": metrics.Gauge(
                "raytpu_serve_prefix_hit_ratio",
                "Cumulative prompt tokens served from the prefix cache "
                "over all prompt tokens admitted (token-weighted hit "
                "ratio).",
            ),
            "prefix_hit_depth": metrics.Histogram(
                "raytpu_serve_prefix_hit_depth_tokens",
                "Per-request prefix-cache hit depth in tokens (0 = "
                "cold prefill) — joins with TTFT for "
                "TTFT-by-hit-depth.",
                boundaries=[1, 16, 32, 64, 128, 256, 512, 1024, 2048,
                            4096],
            ),
            "prefix_cached_pages": metrics.Gauge(
                "raytpu_serve_prefix_cached_pages",
                "Pages currently held by the radix-tree prefix index.",
            ),
            "prefix_evicted": metrics.Counter(
                "raytpu_serve_prefix_evicted_pages_total",
                "Cache pages evicted (refcount-0 LRU) under admission "
                "pressure.",
            ),
            "state_cache_bytes": metrics.Gauge(
                "raytpu_serve_state_cache_bytes",
                "Bytes of per-slot recurrent state (convolution tails "
                "and SSM states of state-space layers, retention "
                "states) in the engine's cache tree, its scratch slot "
                "included; beside the KV pages or, for a model with no "
                "paged layer, all of the cache.  0 for a model with "
                "none.",
            ),
            "state_resets": metrics.Counter(
                "raytpu_serve_state_resets_total",
                "Rows dispatched with row_start == 0 on an engine whose "
                "cache holds recurrent state: each one resets its "
                "slot's state to zero on the device (a new request, or "
                "a preempted one prefilling again from token 0).",
            ),
            # keyed by the cache's counter leaf it is read from
            # (PagedEngineAdapter.counter_leaves)
            "moe_tokens": metrics.Counter(
                "raytpu_serve_moe_expert_tokens_total",
                "(token, expert) pairs a routed expert has served, by "
                "routed layer and expert: read from the counter the "
                "step keeps on the device, when the engine's stats are "
                "asked for.",
                tag_keys=("layer", "expert"),
            ),
            "collective_bytes": metrics.Counter(
                "raytpu_serve_collective_bytes_total",
                "Bytes one shard puts on the wire for decode-step "
                "allreduces, by link class (ici = in-host exact psum, "
                "dcn = cross-daemon leg, int8-quantized unless the "
                "bf16 fallback is configured).  Analytic accounting "
                "(parallel.collectives.allreduce_wire_bytes) so CPU "
                "emulation and real DCN report the same number.",
                tag_keys=("link",),
            ),
            "collective_seconds": metrics.Histogram(
                "raytpu_serve_collective_seconds",
                "Measured wall time of one decode-shaped collective "
                "per link class, observed from startup calibration "
                "probes (the per-step collective inside the fused "
                "decode program is not separately observable from the "
                "host).",
                boundaries=[1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
                            1e-3, 2.5e-3, 5e-3, 1e-2, 5e-2, 0.25, 1.0],
                tag_keys=("link",),
            ),
        }
    else:
        reg = metrics.registry()
        for m in _TELEMETRY.values():
            reg.register(m)
    # The migration/disagg families (serve/kv_transfer) register with
    # the engine so `check_metrics --require` sees them at zero before
    # any page ever moves.
    from ray_tpu.serve import kv_transfer as _kvt

    # The adapter-pool families (serve/adapter_pool) merge the same way
    # so `check_metrics --require` pins them at zero even on engines
    # that never load an adapter.
    from ray_tpu.serve import adapter_pool as _apool

    # The waterfall-attribution + flight-recorder families merge the
    # same way so the tier-1 --require pins see them at zero on engines
    # that never missed an SLO.
    from ray_tpu.serve import latency_attribution as _lat
    from ray_tpu.util import flight_recorder as _frec

    # The doctor families (util/doctor) merge the same way so the
    # tier-1 --require pins see them at zero before any audit runs.
    from ray_tpu.util import doctor as _doc

    out = dict(_TELEMETRY)
    out.update(_kvt._telemetry())
    out.update(_apool._telemetry())
    out.update(_lat._telemetry())
    out.update({f"frec_{k}": v for k, v in _frec._telemetry().items()})
    out.update({f"doctor_{k}": v for k, v in _doc._telemetry().items()})
    return out


@dataclasses.dataclass(frozen=True)
class SLO:
    """Per-request latency objectives; a None bound is unconstrained.
    A request is SLO-met only when it FINISHED inside every set bound —
    failed and cancelled requests always miss, which is what makes the
    goodput gauge honest under churn."""

    ttft_s: Optional[float] = None
    tpot_s: Optional[float] = None
    e2e_s: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    # ragged_batching=True serves through the one ragged step: the only
    # path the benchmark measures.  False (the default, and the only
    # choice under a mesh) serves through the two-program path, which
    # alone reads min_prefill_bucket and decode_chunk.
    max_slots: int = 8
    max_seq_len: int = 1024
    min_prefill_bucket: int = 32
    max_new_tokens_default: int = 128
    eos_id: Optional[int] = None
    # Paged KV cache (block tables over a page pool — TPU PagedAttention,
    # ops/paged_attention.py).  num_pages=0 sizes the pool for full
    # occupancy (slots × max_seq_len); smaller pools oversubscribe and
    # requests queue when no pages are free.
    page_size: int = 64
    num_pages: int = 0
    # Decode this many steps per host round-trip (lax.scan on device).
    # Amortizes host↔device latency; tokens past an EOS inside a chunk
    # are discarded host-side.  Chunk sizes: powers of two ≤ this.
    # (16 was sized for a ~100 ms round trip; not re-measured on a
    # directly attached chip — ROADMAP Queue 1 items 2-3.)
    decode_chunk: int = 16
    # Chunked prefill: prompts longer than this many tokens prefill in
    # segments of this size, interleaved with decode chunks — a long
    # prompt never stalls running streams for its full prefill (0 =
    # always one-shot; the ragged step chunks by token_budget).
    prefill_chunk: int = 0
    # Latency objectives driving the SLO met/missed counters and the
    # goodput gauge (None = every finished request counts as met).
    slo: Optional[SLO] = None
    # Overload shedding: refuse (ShedError) new submissions while the
    # oldest unadmitted request has already waited longer than this —
    # a request queued behind it could only produce a guaranteed-late
    # answer, so fail fast and immediately-retriable instead of
    # timing the client out.  The natural setting is the e2e SLO
    # budget (slo.e2e_s).  None = never shed.
    shed_queue_age_s: Optional[float] = None
    # Ragged batching: one unified device step per
    # dispatch mixing decode rows (1 token per active slot) with
    # prefill chunks from the admission queue, packed up to
    # token_budget tokens (ops/ragged_paged_attention.py).  Replaces
    # the prefill-vs-decode interleave — a long prompt streams in
    # budget-sized chunks beside live decode rows instead of stalling
    # them.  token_budget=0 sizes it max_slots + max(prefill_chunk,
    # page_size).  It bounds what a step may carry (admission and
    # chunking); the program a step runs is compiled for the budget's
    # positions or, where the step's tokens fit, for max_slots rounded
    # up to 8 (ragged_step_shapes), so a decode step does not pay for
    # the chunk it does not carry.
    ragged_batching: bool = False
    token_budget: int = 0
    # Radix-tree prefix cache over the page pool
    # (serve/prefix_index.py): finished requests donate their full KV
    # pages to a refcounted trie; admission matches the longest cached
    # prefix and schedules the ragged prefill from the hit depth
    # instead of token 0.  Requires ragged_batching (prefill-from-
    # offset rides the per-row `start` descriptor of the unified
    # step).  Shared pages are copy-on-write: the only write that can
    # land in one — the last-token re-run of an exact full-prompt hit
    # — splits the page first.  Eviction is refcount-0 LRU, driven by
    # admission pressure so cached pages never starve new requests.
    prefix_cache: bool = False
    # Multi-tenant LoRA multiplexing (serve/adapter_pool.py): sizing of
    # the paged adapter-weight pool backing requests that carry an
    # adapter_id.  Only consulted when the model config enables LoRA
    # (LlamaConfig(lora=...) routes llama_paged_adapter to build a
    # pool + segmented ragged step).  adapter_pool_pages=0 auto-sizes
    # (room for 4 resident adapters); max_batch_adapters bounds the
    # DISTINCT adapters one ragged step can gather (incl. the null
    # row); adapter_int8 stores pool pages int8 with per-page scales.
    adapter_pool_pages: int = 0
    adapter_page_elems: int = 8192
    max_batch_adapters: int = 8
    adapter_int8: bool = False
    # Speculative decoding (requires ragged_batching): each round the
    # engine drafts spec_k tokens autoregressively on a small draft
    # model (LLMEngine(draft_params=..., draft_adapter=...); omitted =
    # self-draft with the target weights — a testing/calibration mode)
    # and verifies all of them in ONE target step by packing them as a
    # k-token prefill-chunk row of the ragged batch, accepting the
    # longest matching prefix plus one free token from the target
    # logits.  Rejection rewinds the slot's host length mirror to the
    # accept boundary — the paged KV rollback; rejected tail positions
    # are overwritten by later steps and never become
    # prefix-cache-visible.  The scheduler gates speculation per round:
    # only greedy base-model rows with no in-flight tokens speculate,
    # never while prefill chunks contend for the token budget, and a
    # cold acceptance EMA (< spec_cold_accept) pauses speculation for
    # spec_cooldown_rounds dispatches before re-probing.  Draft KV
    # lives in a second paged pool of spec_draft_pages pages (0 =
    # full-occupancy auto-sizing) under the same allocator discipline.
    spec_decode: bool = False
    spec_k: int = 4
    spec_draft_pages: int = 0
    spec_cold_accept: float = 0.2
    spec_cooldown_rounds: int = 32

    def buckets(self) -> List[int]:
        out, b = [], self.min_prefill_bucket
        while b < self.max_seq_len:
            out.append(b)
            b *= 2
        out.append(self.max_seq_len)
        return out


@dataclasses.dataclass(frozen=True)
class PagedEngineAdapter:
    """Model plug: how the engine talks to a model family.  The cache is
    pages under block tables (no length field: the engine tracks lengths
    host-side).  A model served on the ragged step provides two entries,
    ``state_bytes_per_slot`` if its cache also holds state by slot, and
    ``paged_kv=False`` if state by slot is all it holds:

    init_cache(num_pages, page_size) -> cache pytree
    ragged_step(params, tokens[T], tok_pos[T], row_slot[R], row_start[R],
        row_len[R], row_off[R], block_tables, cache, *, lora=None,
        logit_idx=None) -> (logits[R,V], cache)
    """

    init_cache: Callable[..., Any]
    # The unified ragged step (EngineConfig.ragged_batching): one device
    # program serving a mixed batch of decode rows (len 1) and prefill
    # chunks.  The engine passes a keyword only on the steps that need
    # it, so a model that takes neither need not name them.
    # lora=(pool, page_table, tok_adapter): the engine's adapter pool
    # (make_adapter_pool), the step's page gather plan and the per-token
    # adapter index, for per-token segmented LoRA deltas
    # (ops/segmented_lora).  logit_idx[Tv]: flat-buffer positions of
    # speculative verify rows' candidates; the step then returns
    # (logits[R,V], verify_logits[Tv,V], cache), the first R
    # bit-identical to the step without it (EngineConfig.spec_decode).
    ragged_step: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    # The two-program path (ragged_batching=False; the only one that
    # runs under a mesh) needs the first two of:
    #   prefill_slot(params, tokens[S], true_len, pages[S/page], cache)
    #       -> (logits[V], cache)
    #   decode_slots(params, tokens[slots], active, block_tables,
    #       lengths, cache) -> (logits[slots, V], cache, new_lengths)
    #   prefill_batch(params, tokens[K,S], true_lens[K],
    #       pages_rows[K,S/page], cache) -> (logits[K,V], cache): one
    #       [K, S] forward instead of a fori_loop of K prefill_slot rows
    #   prefill_chunk(params, tokens[K,C], start[K], chunk_lens[K],
    #       pages_rows[K,maxp], cache) -> (logits[K,V], cache) — enables
    #       EngineConfig.prefill_chunk there.
    prefill_slot: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    decode_slots: Optional[
        Callable[..., Tuple[jax.Array, Any, jax.Array]]] = None
    prefill_batch: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    prefill_chunk: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    # COW split for the prefix cache: copy_page(cache, src, dst) ->
    # cache duplicates ONE physical page (all layers, k+v and any
    # per-page quantization scales) so a writer can diverge from a
    # shared page — enables EngineConfig.prefix_cache.
    copy_page: Optional[Callable[..., Any]] = None
    # Tensor-parallel serving (LLMEngine(mesh=...)): shard_params
    # places params on the mesh (pass HOST arrays for big models — the
    # transfer shards directly, never materializing an unsharded copy
    # on one device); cache_shardings(mesh) returns the sharding tree
    # matching init_cache's output so the engine can ALLOCATE the page
    # pool under it.  GSPMD partitions the jitted programs from these
    # placements; the model's decode attention runs per shard (llama:
    # cfg.tensor_parallel + paged_decode_attention_tp).
    shard_params: Optional[Callable[[Any, Any], Any]] = None
    cache_shardings: Optional[Callable[[Any], Any]] = None
    # Multi-host shard groups: collective_step_bytes(mesh, rows) ->
    # {"ici": bytes, "dcn": bytes} — analytic per-device wire bytes of
    # ONE decode step over ``rows`` active slots, feeding
    # raytpu_serve_collective_bytes_total.  collective_probes(mesh) ->
    # {link: zero-arg callable} running one decode-shaped collective;
    # the engine times them at startup for
    # raytpu_serve_collective_seconds.
    collective_step_bytes: Optional[
        Callable[[Any, int], Dict[str, int]]] = None
    collective_probes: Optional[
        Callable[[Any], Dict[str, Callable]]] = None
    # Multi-tenant LoRA multiplexing: make_adapter_pool(EngineConfig)
    # builds the pool the engine owns (serve/adapter_pool.AdapterPool)
    # and hands back to ragged_step as lora=; set iff the model config
    # enables LoRA.
    make_adapter_pool: Optional[Callable[[Any], Any]] = None
    # weight_routes(params) -> {"in_place": [...], "sliced": [...]} or
    # None: which weight operands the ragged step's layer kernel reads
    # where they are stored and which are copied out of the stack for
    # every layer of every step.  The engine says it once at start-up.
    weight_routes: Optional[
        Callable[[Any], Optional[Dict[str, List[str]]]]] = None
    # ragged_grid_cells(row_start, row_len, maxp, page, lora) -> int:
    # the attention cells the ragged step's kernel walks for one step's
    # packed row arrays ([R] each; ``lora`` says the step carries
    # adapters).  None = the page table's capacity, R * (maxp + 1),
    # whatever the rows hold.  ``llm.pack`` reports it as grid_cells.  It
    # counts PAGES: a kernel whose cell spans several pages of a row (the
    # latent walk's) counts every page the cell's tile spans, the tail
    # past the row's last page included, so that live_cells over it says
    # how full the cells are.
    ragged_grid_cells: Optional[Callable[..., int]] = None
    # ragged_sel_tokens(row_start, row_len) -> int: the cached positions
    # a SPARSE attention selects for one step's packed rows, summed over
    # its query tokens (``min(position + 1, topk)`` each); None = the
    # model attends to every cached position.  ``llm.pack`` reports it
    # as sel_tokens.
    ragged_sel_tokens: Optional[Callable[..., int]] = None
    # The longest row of fresh tokens the model's ragged step takes; None
    # = any.  A row may take the whole token budget, so the engine
    # refuses a budget past it.
    max_row_tokens: Optional[int] = None
    # Bytes of recurrent state one sequence holds per slot, whatever its
    # length (state-space layers: convolution tails, SSM states); 0 = the
    # cache is KV pages only.  Non-zero, the engine calls
    # init_cache(num_pages, page_size, max_slots) and the cache tree
    # holds that state indexed by slot beside the page pools; the ragged
    # step resets a slot's state where a row has row_start == 0.  State
    # that is a function of every token so far cannot be shared by
    # prefix, rewound, or shipped as pages, so the engine refuses the
    # prefix cache, speculative decoding and KV migration for it.
    state_bytes_per_slot: int = 0
    # The cache tree's leaves that hold that state (indexed by slot);
    # every other leaf is a page pool or its scales.
    state_leaves: Tuple[str, ...] = ()
    # False = no layer of the model keeps K/V by token: the cache is
    # state by slot and nothing else (state_bytes_per_slot says how
    # much).  The engine then sizes no page pool (init_cache is called
    # with 0 pages), builds block tables of no column, admits by free
    # slots alone, and its page counters read 0.
    paged_kv: bool = True
    # The cache tree's leaves that are counters the step adds to on the
    # device (pairs an expert has served, ...): neither pages nor state
    # by slot.  The engine leaves them out of its byte counts and reads
    # them only when asked (``stats()["model_counters"]``); a leaf
    # ``[layers, experts]`` named as one of the engine's own counters
    # (``moe_tokens``) also feeds that counter.
    counter_leaves: Tuple[str, ...] = ()


def llama_paged_adapter(cfg, lora_loader=None) -> PagedEngineAdapter:
    """``lora_loader`` (adapter_id -> factor pytree / flat vector)
    feeds the adapter pool when cfg.lora is set; None uses the
    deterministic seeded loader (segmented_lora.default_adapter_loader),
    which every replica resolves identically — the property adapter
    failover relies on."""
    from ray_tpu.models import llama
    from ray_tpu.ops.ragged_paged_attention import live_cell_count

    make_adapter_pool = None
    if getattr(cfg, "lora", None) is not None:
        from ray_tpu.serve.adapter_pool import AdapterPool

        def make_adapter_pool(ecfg):
            return AdapterPool(
                cfg, cfg.lora,
                num_pages=ecfg.adapter_pool_pages,
                page_elems=ecfg.adapter_page_elems,
                max_batch_adapters=ecfg.max_batch_adapters,
                int8=ecfg.adapter_int8,
                loader=lora_loader)

    def gathered(lora):
        """The engine's ``lora=(pool, page_table, tok_adapter)`` as the
        ``(stacks, tok_adapter, scale)`` ragged_step_paged takes: the
        step's adapters gathered out of the pool's pages."""
        if lora is None:
            return None
        if getattr(cfg, "lora", None) is None:
            raise ValueError(
                "ragged_step got lora= but the model config has no "
                "lora= (LlamaConfig.lora)")
        from ray_tpu.ops import segmented_lora as _sl

        pool, page_table, tok_adapter = lora
        flat = _sl.gather_adapter_flat(pool, page_table)
        stacks = _sl.gather_adapter_stacks(flat, cfg, cfg.lora)
        return stacks, tok_adapter, cfg.lora.scale

    return PagedEngineAdapter(
        init_cache=lambda num_pages, page: llama.init_paged_cache(
            cfg, num_pages, page
        ),
        ragged_step=lambda params, tokens, tok_pos, row_slot, row_start,
        row_len, row_off, bt, cache, *, lora=None, logit_idx=None:
            llama.ragged_step_paged(params, tokens, tok_pos, row_slot,
                                    row_start, row_len, row_off, bt, cfg,
                                    cache, lora=gathered(lora),
                                    logit_idx=logit_idx),
        make_adapter_pool=make_adapter_pool,
        prefill_slot=lambda params, tokens, true_len, pages, cache:
            llama.prefill_slot_paged(params, tokens, true_len, pages,
                                     cfg, cache),
        decode_slots=lambda params, tokens, active, bt, lens, cache:
            llama.decode_slots_paged(params, tokens, active, bt, lens,
                                     cfg, cache),
        prefill_batch=lambda params, tokens, true_lens, pages_rows, cache:
            llama.prefill_batch_paged(params, tokens, true_lens,
                                      pages_rows, cfg, cache),
        prefill_chunk=lambda params, tokens, start, chunk_lens, pages_rows,
        cache:
            llama.prefill_chunk_paged(params, tokens, start, chunk_lens,
                                      pages_rows, cfg, cache),
        copy_page=llama.copy_page_paged,
        shard_params=lambda params, mesh:
            llama.shard_params_for_serving(params, cfg, mesh),
        cache_shardings=lambda mesh: llama.paged_cache_shardings(
            mesh, kv_int8=cfg.kv_int8),
        collective_step_bytes=lambda mesh, rows:
            llama.decode_collective_bytes(cfg, mesh, rows),
        collective_probes=lambda mesh:
            llama.serving_collective_probes(cfg, mesh),
        weight_routes=lambda params:
            llama.ragged_weight_routes(params, cfg),
        # every route of the step (fused, unfused, LoRA) walks the rows'
        # pooled pages plus a self cell a row
        ragged_grid_cells=lambda row_start, row_len, maxp, page, lora:
            live_cell_count(row_start, row_len, page),
    )


def jamba_paged_adapter(cfg) -> PagedEngineAdapter:
    """Jamba (models/jamba.py): Mamba-1 layers with per-slot recurrent
    state beside paged KV for its few attention layers.  Its step takes
    neither ``lora=`` nor ``logit_idx=``, and the two-program path has
    no recurrent-state form."""
    from ray_tpu.models import jamba
    from ray_tpu.ops.ragged_paged_attention import live_cell_count

    return PagedEngineAdapter(
        init_cache=lambda num_pages, page, max_slots: jamba.init_cache(
            cfg, num_pages, page, max_slots),
        ragged_step=lambda params, tokens, tok_pos, row_slot, row_start,
        row_len, row_off, bt, cache:
            jamba.ragged_step(params, tokens, tok_pos, row_slot,
                              row_start, row_len, row_off, bt, cfg, cache),
        ragged_grid_cells=lambda row_start, row_len, maxp, page, lora:
            live_cell_count(row_start, row_len, page),
        state_bytes_per_slot=cfg.state_bytes_per_slot(),
        state_leaves=("conv", "ssm"),
    )


def brumby_paged_adapter(cfg) -> PagedEngineAdapter:
    """Brumby (models/brumby.py): every mixer is a power-retention
    layer whose past is a matrix and a vector per KV head and slot, so
    the cache has no page at all.  Its step takes neither ``lora=`` nor
    ``logit_idx=``, and the two-program path has no recurrent-state
    form."""
    from ray_tpu.models import brumby

    return PagedEngineAdapter(
        init_cache=lambda num_pages, page, max_slots: brumby.init_cache(
            cfg, num_pages, page, max_slots),
        ragged_step=lambda params, tokens, tok_pos, row_slot, row_start,
        row_len, row_off, bt, cache:
            brumby.ragged_step(params, tokens, tok_pos, row_slot,
                               row_start, row_len, row_off, bt, cfg, cache),
        state_bytes_per_slot=cfg.state_bytes_per_slot(),
        state_leaves=("ret_s", "ret_z"),
        paged_kv=False,
    )


def xing_paged_adapter(cfg) -> PagedEngineAdapter:
    """Xing4.0 (models/xing.py): latent attention over ONE page pool
    (``kv_c``: a token's ``c | kr``, no k and v), routed experts with no
    token dropped, a four-stream residual.  The cache is pages and
    nothing by slot, so requests are admitted and pages accounted as for
    any paged model.  Its step takes neither ``lora=`` nor ``logit_idx=``
    and it has no ``copy_page``, two-program path or shardings: the
    engine refuses the prefix cache (and KV migration with it),
    speculative decoding and a mesh for it."""
    from ray_tpu.models import xing
    from ray_tpu.ops.latent_attention import latent_cell_count

    def init_cache(num_pages, page):
        from ray_tpu.util import flight_recorder

        cache = xing.init_cache(cfg, num_pages, page)
        experts = 3 * cfg.dim * cfg.moe_dim * jnp.dtype(
            cfg.param_dtype).itemsize
        flight_recorder.record(
            "serve_model_parts", model="xing",
            expert_bytes=experts, experts_per_layer=cfg.n_experts,
            routed_layers=cfg.n_moe,
            routed_expert_bytes=experts * cfg.n_experts * cfg.n_moe,
            latent_pool_bytes=int(cache["kv_c"].size
                                  * cache["kv_c"].dtype.itemsize),
            latent_bytes_per_token=cfg.n_layers * cfg.pool_width
            * cache["kv_c"].dtype.itemsize)
        return cache

    return PagedEngineAdapter(
        init_cache=init_cache,
        ragged_step=lambda params, tokens, tok_pos, row_slot, row_start,
        row_len, row_off, bt, cache:
            xing.ragged_step(params, tokens, tok_pos, row_slot, row_start,
                             row_len, row_off, bt, cfg, cache),
        ragged_grid_cells=lambda row_start, row_len, maxp, page, lora:
            latent_cell_count(row_start, row_len, page, cfg.n_heads, maxp),
        counter_leaves=("moe_tokens", "moe_distinct"),
    )


def glm5_paged_adapter(cfg) -> PagedEngineAdapter:
    """GLM-5 (models/glm5.py): latent attention kept to the positions a
    learned indexer selects, over TWO page pools under the same block
    tables (``kv_c``: a token's ``c | kr``; ``kv_i``: its index key), and
    routed experts of which this chip holds ``cfg.n_experts``.  The cache
    is pages and nothing by slot.  As for ``xing_paged_adapter`` the
    engine refuses the prefix cache (and KV migration with it: its
    programs ship ``k``/``v`` by name), speculative decoding and a mesh."""
    from ray_tpu.models import glm5
    from ray_tpu.ops.dsa_index import sel_token_count
    from ray_tpu.ops.latent_attention import sparse_cell_count

    def init_cache(num_pages, page):
        from ray_tpu.util import flight_recorder

        cache = glm5.init_cache(cfg, num_pages, page)
        experts = 3 * cfg.dim * cfg.moe_dim * jnp.dtype(
            cfg.param_dtype).itemsize
        flight_recorder.record(
            "serve_model_parts", model="glm5",
            expert_bytes=experts, experts_per_layer=cfg.n_experts,
            experts_of_layer=cfg.n_routed, routed_layers=cfg.n_moe,
            routed_expert_bytes=experts * cfg.n_experts * cfg.n_moe,
            latent_pool_bytes=int(cache["kv_c"].size
                                  * cache["kv_c"].dtype.itemsize),
            index_pool_bytes=int(cache["kv_i"].size
                                 * cache["kv_i"].dtype.itemsize))
        return cache

    return PagedEngineAdapter(
        init_cache=init_cache,
        ragged_step=lambda params, tokens, tok_pos, row_slot, row_start,
        row_len, row_off, bt, cache:
            glm5.ragged_step(params, tokens, tok_pos, row_slot, row_start,
                             row_len, row_off, bt, cfg, cache),
        ragged_grid_cells=lambda row_start, row_len, maxp, page, lora:
            sparse_cell_count(row_start, row_len, page, cfg.n_heads, maxp),
        ragged_sel_tokens=lambda row_start, row_len:
            sel_token_count(row_start, row_len, cfg.index_topk),
        counter_leaves=("moe_tokens", "moe_distinct"),
    )


def sala_paged_adapter(cfg) -> PagedEngineAdapter:
    """MiniCPM-SALA (models/minicpm_sala.py): lightning layers with a
    matrix state by slot (``lin_s``) beside the sparse layers' page pools
    (``k``/``v`` and ``kh``, their compressed keys, under the same block
    tables) and two counters, of the pages the walk read and of the
    cells it read them in.  As for
    ``jamba_paged_adapter`` the state is why the engine refuses the
    prefix cache, speculative decoding and migration; its step takes
    neither ``lora=`` nor ``logit_idx=``."""
    from ray_tpu.models import minicpm_sala as sala
    from ray_tpu.ops.block_sparse_attention import (
        sel_token_count,
        walk_page_count,
    )

    def init_cache(num_pages, page, max_slots):
        from ray_tpu.util import flight_recorder

        flight_recorder.record(
            "serve_model_parts", model="minicpm_sala",
            state_bytes_per_slot=cfg.state_bytes_per_slot(),
            pool_bytes_per_token=cfg.pool_bytes_per_token(),
            lightning_layers=cfg.layer_kinds().count(sala.LIGHTNING),
            sparse_layers=cfg.layer_kinds().count(sala.SPARSE))
        return sala.init_cache(cfg, num_pages, page, max_slots)

    return PagedEngineAdapter(
        init_cache=init_cache,
        ragged_step=lambda params, tokens, tok_pos, row_slot, row_start,
        row_len, row_off, bt, cache:
            sala.ragged_step(params, tokens, tok_pos, row_slot, row_start,
                             row_len, row_off, bt, cfg, cache),
        # pool pages the walk reads in one sparse layer, over rows and KV
        # heads, plus a self cell a (row, KV head)
        ragged_grid_cells=lambda row_start, row_len, maxp, page, lora:
            walk_page_count(row_start, row_len, cfg.n_kv_heads, cfg.sparse,
                            page)
            + cfg.n_kv_heads * int(sum(1 for n in row_len if n > 0)),
        ragged_sel_tokens=lambda row_start, row_len:
            sel_token_count(row_start, row_len, cfg.sparse),
        # the walk's self cell is plain causal attention: a row's fresh
        # tokens have to lie inside the forced window of its last
        max_row_tokens=cfg.sparse.max_row_tokens,
        state_bytes_per_slot=cfg.state_bytes_per_slot(),
        state_leaves=("lin_s",),
        counter_leaves=("sel_pages", "walk_cells"),
    )


def _sample(logits: jax.Array, temperature: jax.Array,
            key: jax.Array) -> jax.Array:
    """logits [..., V], temperature broadcastable — greedy at temp 0,
    categorical otherwise; computed on device."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1)
        scaled = logits / jnp.maximum(temperature, 1e-6)[..., None]
        sampled = jax.random.categorical(key, scaled, axis=-1)
        return jnp.where(temperature <= 0.0, greedy,
                         sampled).astype(jnp.int32)


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    stream: "queue.Queue"
    req_id: int
    submitted_at: float = dataclasses.field(default_factory=time.monotonic)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    # Beside ``tokens``: the ordinal of the engine step whose execution
    # produced each one (a speculative round's tokens share one),
    # appended before the token enters ``stream``, so whoever takes the
    # n-th token off the stream finds its step here.
    token_seqs: List[int] = dataclasses.field(default_factory=list)
    # Telemetry: the submitter's span context (None when tracing is
    # off) and the prefill-dispatch stamp splitting queue wait from
    # prefill in the request's span tree.
    trace_ctx: Optional[Dict[str, str]] = None
    admitted_at: Optional[float] = None
    # End-to-end id labeling the ring, spans, and log lines (minted at
    # the serve router, or locally when submitted straight to the
    # engine); incremental inter-token-gap tracking rides _emit.
    request_id: str = ""
    last_token_at: Optional[float] = None
    max_itl_s: float = 0.0
    # Prompt tokens served from the prefix cache (0 = cold prefill);
    # stamped at admission, mirrored to the request ring so
    # TTFT-by-hit-depth is observable downstream.
    prefix_hit: int = 0
    # Multi-tenant multiplexing: the LoRA adapter this request decodes
    # under ("" = base model).  Rides the ring rows and the per-row
    # descriptor of the ragged step.
    adapter_id: str = ""
    # Speculative decoding: tokens this request drafted / had accepted
    # across its verify rounds (0/0 = never speculated).  Mirrored to
    # the ring as the `spec` column of `raytpu list requests`.
    spec_drafted: int = 0
    spec_accepted: int = 0

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


_DONE = object()


class CompletionStream:
    """Client view of one request: iterate tokens as they generate."""

    def __init__(self, req: Request, engine: "Optional[LLMEngine]" = None):
        self._req = req
        self._engine = engine
        self._done = threading.Event()
        self._taken = 0   # tokens taken off the stream, by either way

    @property
    def request_id(self) -> str:
        return self._req.request_id

    def cancel(self) -> None:
        """Ask the engine to cancel this request (idempotent; a no-op
        once the request is terminal).  The stream still ends with its
        normal _DONE marker — tokens emitted before the cancel took
        effect stay delivered."""
        if self._engine is not None:
            self._engine.cancel(self._req.request_id)

    def __iter__(self):
        while not self._done.is_set():
            item = self._req.stream.get()
            if item is _DONE:
                self._done.set()
                return
            if isinstance(item, BaseException):
                self._done.set()
                raise item
            # From the token handed on to the consumer asking for the
            # next, on the consumer's thread: in a replica that is the
            # item's way out (the deployment's own work on it, its
            # encode and its seal), named by the step it came from.
            seq = self._req.token_seqs[self._taken]
            self._taken += 1
            with tracing.span("serve.stream_item", record=False,
                              attributes={"seq": seq}):
                yield item

    def result(self, timeout_s: Optional[float] = None) -> List[int]:
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        while not self._done.is_set():
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            try:
                item = self._req.stream.get(timeout=remaining)
            except queue.Empty:
                raise TimeoutError(
                    f"generation not finished within {timeout_s}s "
                    f"({len(self._req.tokens)} tokens so far)"
                ) from None
            if item is _DONE:
                self._done.set()
            elif isinstance(item, BaseException):
                self._done.set()
                raise item
            else:
                self._taken += 1
        return list(self._req.tokens)

    @property
    def metrics(self) -> Dict[str, Any]:
        r = self._req
        return {
            "ttft_s": r.ttft_s,
            "total_s": (None if r.finished_at is None
                        else r.finished_at - r.submitted_at),
            "num_tokens": len(r.tokens),
        }


class LLMServer:
    """Ready-made Serve deployment hosting an LLMEngine.

    Request payload: {"tokens": [...], "max_new_tokens"?: int,
    "temperature"?: float} → {"tokens": [...], "metrics": {...}}.
    Use with ``serve.deployment(...)(LLMServer).bind(cfg, engine_cfg,
    param_loader)`` — param_loader runs inside the replica so weights
    never travel through the object store.
    """

    def __init__(self, model_cfg: Any, engine_cfg: EngineConfig,
                 param_loader: Callable[[], Any], *, adapter_factory:
                 Callable[[Any], PagedEngineAdapter] = None,
                 draft_param_loader: Callable[[], Any] = None,
                 draft_model_cfg: Any = None):
        # Rank 0 of a shard group (serve/shard_group.py) hosts the
        # engine over a hybrid DCN×ICI serving mesh: weights
        # tensor-parallel over tp (in host) × dcn_tp (across group
        # members), KV pools sharded along heads, decode's DCN
        # allreduce legs int8-quantized unless the group configured
        # the bf16 fallback.
        from ray_tpu.serve.shard_group import current_shard_group

        sg = current_shard_group()
        mesh = None
        if sg is not None:
            import dataclasses as _dc

            from ray_tpu.parallel.mesh import create_serving_mesh

            model_cfg = _dc.replace(
                model_cfg, tensor_parallel=True,
                dcn_quantized_allreduce=sg.quantized)
            mesh = create_serving_mesh(sg.size, sg.tensor_parallel)
        # Disaggregated prefill/decode role (serve/kv_transfer),
        # installed by the hosting ReplicaActor the same way the shard
        # group is.  Roles need the prefix trie: migrated pages are
        # identified and resumed through its chained path hashes.
        from ray_tpu.serve.kv_transfer import current_disagg

        self._disagg = current_disagg()
        if (self._disagg is not None
                and self._disagg.role != "unified"
                and not engine_cfg.prefix_cache):
            raise ValueError(
                "disaggregated serving roles require "
                "EngineConfig.prefix_cache=True (KV migration is keyed "
                "by the prefix trie's chained path hashes)")
        # Replica-local mirrors of the disagg counters: replicas run as
        # separate actor processes, so tests and the state API read
        # these through disagg_stats() instead of scraping the
        # replica's own Prometheus registry.
        self._handoff_counts = {"migrated": 0, "failed": 0, "local": 0}
        self._disagg_requests = 0
        # Round-robin fallback for handoff-target spreading when a
        # payload carries no request id to hash.
        self._handoff_rr = itertools.count()
        make_adapter = adapter_factory or llama_paged_adapter
        # Speculative decoding's draft model loads inside the replica
        # like the target (weights never cross the object store).  No
        # loader + spec_decode=True = the engine self-drafts.
        draft_params = (draft_param_loader()
                        if draft_param_loader is not None else None)
        draft_adapter = None
        if draft_params is not None:
            draft_adapter = make_adapter(draft_model_cfg
                                         if draft_model_cfg is not None
                                         else model_cfg)
        adapter = make_adapter(model_cfg)
        if (self._disagg is not None and self._disagg.role != "unified"
                and adapter.state_bytes_per_slot):
            raise ValueError(
                f"disaggregated serving role {self._disagg.role!r} hands "
                "a request over by migrating its KV pages; this model's "
                "cache holds per-slot recurrent state that no page "
                "carries")
        with tracing.span("llm.load_weights", startup=True):
            params = jax.block_until_ready(param_loader())
        self.engine = LLMEngine(
            params, adapter, engine_cfg,
            mesh=mesh, draft_params=draft_params,
            draft_adapter=draft_adapter,
        )

    @staticmethod
    def _adapter_id(payload: Dict[str, Any]) -> str:
        """The request's LoRA adapter id: explicit payload key > the
        multiplexed model id the replica installed from request
        metadata (handle.options(multiplexed_model_id=...) -> router
        metadata -> serve/multiplex contextvar) > "" (base model)."""
        from ray_tpu.serve import multiplex as _mux

        return (payload.get("adapter_id")
                or _mux.get_multiplexed_model_id() or "")

    def __call__(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        # Explicit payload id > the id the replica installed from
        # request metadata (the router-minted one) > engine-local mint.
        stream = self.engine.submit(
            payload["tokens"],
            max_new_tokens=payload.get("max_new_tokens"),
            temperature=payload.get("temperature", 0.0),
            request_id=payload.get("request_id"),
            adapter_id=self._adapter_id(payload),
        )
        tokens = stream.result()
        return {"tokens": tokens, "metrics": stream.metrics,
                "request_id": stream.request_id}

    def stream(self, payload: Dict[str, Any]):
        """Streaming entry (serve data plane, ``stream=True`` handles):
        yields tokens as the engine generates them.  A preemption
        surfaces as PreemptedError AFTER every already-generated token
        has been yielded, so the router's failover knows the exact
        delivered prefix.

        On a prefill-role replica a fresh request runs the handoff
        protocol instead: prefill + the first handoff_after_tokens
        tokens here, migrate the KV pages to a decode replica, then
        raise MigrationHandoff so the client generator resumes the
        stream there (the migrated prefix is a cache hit — no
        recompute).  ANY transfer failure degrades to a plain
        PreemptedError: the PR-5 continuation replay recomputes
        locally, the stream never stalls."""
        dis = self._disagg
        if dis is not None and dis.role != "unified":
            from ray_tpu.serve.kv_transfer import _telemetry as _kvt_tm

            _kvt_tm()["disagg_requests"].inc(tags={"role": dis.role})
            self._disagg_requests += 1
            if (dis.role == "prefill"
                    and not payload.get("_disagg_resumed")):
                yield from self._stream_prefill_handoff(payload)
                return
        stream = self.engine.submit(
            payload["tokens"],
            max_new_tokens=payload.get("max_new_tokens"),
            temperature=payload.get("temperature", 0.0),
            request_id=payload.get("request_id"),
            adapter_id=self._adapter_id(payload),
        )
        for tok in stream:
            yield tok

    def _pick_decode_target(self, request_id: Optional[str] = None):
        """(replica_id, handle) of one RUNNING decode-role replica of
        this deployment, or None (controller gone, none running, …) —
        checked BEFORE the truncated local submit so a missing target
        degrades to unified serving, not a wasted handoff.

        Least-loaded first: the controller returns each candidate's
        last-pushed num_ongoing_requests next to its handle, so
        handoffs chase live decode capacity instead of hashing blindly
        across a fleet whose load the census order knows nothing
        about.  The request-id hash only breaks ties between
        equally-loaded candidates (deterministic per request, so
        concurrent retries of one handoff agree); payloads without an
        id round-robin the tie instead."""
        import zlib

        from ray_tpu.core import api
        from ray_tpu.serve.controller import CONTROLLER_NAME

        dis = self._disagg
        try:
            controller = api.get_actor(CONTROLLER_NAME)
            rows = api.get(controller.migration_targets.remote(
                dis.app_name, dis.deployment_name, role="decode",
                exclude=[dis.replica_id], with_load=True), timeout=2.0)
        except Exception:
            return None
        if not rows:
            return None
        low = min(row[2] for row in rows)
        best = [row for row in rows if row[2] <= low]
        if request_id:
            idx = zlib.crc32(str(request_id).encode()) % len(best)
        else:
            idx = next(self._handoff_rr) % len(best)
        return best[idx][0], best[idx][1]

    def _stream_prefill_handoff(self, payload: Dict[str, Any]):
        from ray_tpu.core import api
        from ray_tpu.serve import kv_transfer as _kvt

        dis = self._disagg
        tm = _kvt._telemetry()
        requested = payload.get("max_new_tokens")
        if requested is None:
            requested = self.engine.config.max_new_tokens_default
        target = self._pick_decode_target(payload.get("request_id"))
        if target is None or requested <= dis.handoff_after_tokens:
            # No decode replica (yet) or nothing left to hand off:
            # serve unified locally rather than stall.
            tm["disagg_handoffs"].inc(tags={"outcome": "local"})
            self._handoff_counts["local"] += 1
            stream = self.engine.submit(
                payload["tokens"],
                max_new_tokens=requested,
                temperature=payload.get("temperature", 0.0),
                request_id=payload.get("request_id"),
                adapter_id=self._adapter_id(payload),
            )
            for tok in stream:
                yield tok
            return
        # Phase 1: prefill + first tokens locally.  The request
        # FINISHES here, so its prompt pages land in the prefix trie
        # (the finish path donates full pages) — exactly what the
        # lease below pins and exports.
        stream = self.engine.submit(
            payload["tokens"],
            max_new_tokens=dis.handoff_after_tokens,
            temperature=payload.get("temperature", 0.0),
            request_id=payload.get("request_id"),
            adapter_id=self._adapter_id(payload),
        )
        delivered: List[int] = []
        for tok in stream:
            delivered.append(tok)
            yield tok
        # The stream may have finished NATURALLY inside phase 1 (eos or
        # the max_seq_len cap within the first handoff_after_tokens
        # tokens).  Handing off anyway would resume it on the decode
        # replica and generate past the finish — outputs must stay
        # byte-identical to unified serving, so end the stream here.
        eos_id = self.engine.config.eos_id
        if (len(delivered) < dis.handoff_after_tokens
                or (eos_id is not None and delivered
                    and int(delivered[-1]) == eos_id)
                or (len(payload["tokens"]) + len(delivered)
                    >= self.engine.config.max_seq_len)):
            tm["disagg_handoffs"].inc(tags={"outcome": "local"})
            self._handoff_counts["local"] += 1
            return
        # Phase 2: migrate the request's cached pages to the target.
        target_id, handle = target
        seq = list(payload["tokens"]) + [int(t) for t in delivered]
        mig_tokens = seq[:max(len(seq) - 1, 0)]
        budget = dis.migration_timeout_s
        migrated = False
        try:
            lease = self.engine.migration_lease(mig_tokens,
                                                timeout_s=budget)
            if lease is not None:
                try:
                    transfer = self.engine.migration_export(
                        lease["lease_id"], mode=dis.transfer,
                        timeout_s=budget)
                    ref = handle.handle_request.remote(
                        "ingest_kv_transfer", (transfer,), {}, None)
                    api.get(ref, timeout=budget)
                    migrated = True
                finally:
                    self.engine.migration_release(lease["lease_id"],
                                                  timeout_s=budget)
        except Exception as e:
            log.warning("kv migration to %s failed (%r): falling back "
                        "to local recompute", target_id, e)
        continuation = {"prompt": list(payload["tokens"]),
                        "tokens": list(delivered),
                        "temperature": payload.get("temperature", 0.0),
                        "request_id": payload.get("request_id"),
                        "adapter_id": self._adapter_id(payload)}
        if migrated:
            tm["disagg_handoffs"].inc(tags={"outcome": "migrated"})
            self._handoff_counts["migrated"] += 1
            raise _kvt.MigrationHandoff(
                "prefill finished: KV pages migrated, resume on the "
                "decode replica", continuation=continuation,
                target_replica_id=target_id)
        tm["disagg_handoffs"].inc(tags={"outcome": "failed"})
        self._handoff_counts["failed"] += 1
        raise PreemptedError(
            "kv migration failed: resume via local recompute",
            continuation=continuation)

    def disagg_stats(self) -> Dict[str, Any]:
        """Replica-local disaggregation counters (role, requests
        entering under a role, handoff outcomes, migration traffic) —
        the RPC-readable mirror of the raytpu_serve_disagg_* and
        raytpu_serve_kv_migration_* families."""
        dis = self._disagg
        return {
            "role": dis.role if dis is not None else "unified",
            "requests": self._disagg_requests,
            "handoffs": dict(self._handoff_counts),
            "kv_migration": self.engine.stats().get("kv_migration", {}),
        }

    def ingest_kv_transfer(self, transfer: Dict[str, Any]) -> int:
        """Replica-to-replica RPC target: land one migration transfer
        in this engine's pool.  Returns pages ingested."""
        return self.engine.migration_ingest(transfer)

    def export_hot_prefixes(self, max_pages: int = 256,
                            mode: str = "int8") -> List[Dict[str, Any]]:
        """Replica-to-replica RPC target: serialize this engine's hot
        cached prefixes (prefix migration, source side)."""
        return self.engine.export_hot_prefixes(max_pages=max_pages,
                                               mode=mode)

    def pull_prefix_cache(self, max_pages: int = 256, *,
                          app_name: Optional[str] = None,
                          deployment_name: Optional[str] = None,
                          replica_id: Optional[str] = None,
                          transfer: Optional[str] = None,
                          timeout_s: Optional[float] = None) -> int:
        """Prefix migration, destination side: pull hot prefixes from
        the warmest peer replica (longest published prefix summary)
        into the local pool instead of recomputing them.  Returns pages
        ingested; 0 when there is no peer or nothing to pull.

        Identity normally comes from the ambient disagg context; the
        explicit keyword identity is the autoscaler's warm-start path —
        the controller knows who the new replica is and calls this on
        it right after it reaches RUNNING, so a scaled-up group starts
        with the fleet's hot prefixes instead of a cold trie."""
        from ray_tpu.core import api
        from ray_tpu.serve.controller import CONTROLLER_NAME

        dis = self._disagg
        if dis is not None:
            app_name = app_name or dis.app_name
            deployment_name = deployment_name or dis.deployment_name
            replica_id = replica_id or dis.replica_id
            transfer = transfer or dis.transfer
            if timeout_s is None:
                timeout_s = dis.migration_timeout_s
        transfer = transfer or "int8"
        timeout_s = 5.0 if timeout_s is None else timeout_s
        if (self.engine._prefix is None
                or not (app_name and deployment_name and replica_id)):
            return 0
        try:
            controller = api.get_actor(CONTROLLER_NAME)
            rows = api.get(controller.migration_targets.remote(
                app_name, deployment_name, role=None,
                exclude=[replica_id], with_summary=True),
                timeout=2.0)
        except Exception:
            return 0
        rows = [r for r in rows if r[2]]  # peers with a summary
        if not rows:
            return 0
        # Warmest peer = most published path hashes.
        rows.sort(key=lambda r: (-len(r[2].get("hashes", ())), r[0]))
        _, handle, _ = rows[0]
        try:
            transfers = api.get(handle.handle_request.remote(
                "export_hot_prefixes", (max_pages, transfer),
                {}, None), timeout=timeout_s)
        except Exception:
            return 0
        total = 0
        for transfer in transfers:
            try:
                total += self.engine.migration_ingest(transfer)
            except Exception as e:
                log.warning("prefix-migration ingest failed: %r", e)
        return total

    def drain(self, grace_s: float = 5.0) -> int:
        """Preemption notice: drain the engine (stop admitting, evict
        long requests with continuations).  Called by the replica's
        drain path."""
        return self.engine.drain(grace_s)

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def pressure(self) -> Dict[str, Any]:
        """SLO-pressure signals for the autoscaling policy, polled by
        the hosting ReplicaActor's metrics push loop next to
        num_ongoing_requests: the engine's admission-queue age (the
        leading overload signal), cumulative goodput ratio (the
        trailing guard; None until a request reaches a terminal state)
        and cumulative arrival count (the predictive signal — its
        slope moves before any queue forms)."""
        return {"queue_age_s": self.engine.admission_queue_age(),
                "goodput": self.engine.goodput_ratio(),
                "arrivals": self.engine.arrivals_total()}

    def prefix_summary(self) -> Optional[Dict[str, Any]]:
        """Prefix-cache routing summary (None when the cache is off).
        The hosting ReplicaActor polls this and pushes changes to the
        controller for cache-aware routing."""
        return self.engine.prefix_summary()

    def adapter_summary(self) -> Optional[Dict[str, Any]]:
        """Resident-adapter routing summary (None when LoRA
        multiplexing is off).  The hosting ReplicaActor polls this and
        pushes changes to the controller for adapter-affinity
        routing — the same path prefix_summary rides."""
        return self.engine.adapter_summary()

    def doctor(self, deep: bool = True) -> Dict[str, Any]:
        """Run one invariant audit over the hosted engine and return
        its report — the per-replica RPC target behind the
        controller's doctor() fan-out (``GET /api/v0/doctor`` /
        ``raytpu doctor --deep``)."""
        return self.engine.doctor(deep=deep)

    def check_health(self) -> None:
        if self.engine._stopped.is_set():
            raise RuntimeError("engine stopped")
        # A critical invariant violation (a corrupted page partition /
        # refcount) from the most recent audit fails the health
        # verdict: the controller restarts a replica whose KV pool can
        # silently corrupt streams.  Leaks and census drift (error /
        # warning) alert through metrics instead of a restart.
        critical = self.engine._auditor.last_critical()
        if critical:
            v = critical[0]
            raise RuntimeError(
                f"doctor: invariant {v['check']} violated "
                f"({v['subject']}: expected {v['expected']!r}, got "
                f"{v['actual']!r}; {len(critical)} critical total)")


_ENGINE_IDS = itertools.count()


class LLMEngine:
    """Continuous-batching scheduler around jitted prefill/decode."""

    @tracing.in_startup_span("llm.engine_init")
    def __init__(self, params: Any, adapter: PagedEngineAdapter,
                 config: EngineConfig, *, seed: int = 0, mesh: Any = None,
                 draft_params: Any = None,
                 draft_adapter: Optional["PagedEngineAdapter"] = None):
        self.config = config
        self.adapter = adapter
        self._params = params
        # Speculative decoding is armed by _init_spec at the end of the
        # ragged setup; every other mode must still see the flag.
        self._spec_on = False
        # Tensor-parallel serving: engine state lives sharded over the
        # mesh; GSPMD partitions every program from the placements and
        # the model's decode attention runs per shard (parity: serving
        # a model bigger than one chip — SURVEY §7 phase 7).
        self._mesh = mesh
        if mesh is not None and adapter.shard_params is not None:
            self._params = params = adapter.shard_params(params, mesh)
        # Per-slot recurrent state in the cache (see PagedEngineAdapter.
        # state_bytes_per_slot): what the engine may not do with it.
        self._state_bytes_per_slot = int(adapter.state_bytes_per_slot)
        self._ragged_grid_cells = adapter.ragged_grid_cells
        self._ragged_sel_tokens = adapter.ragged_sel_tokens
        self._state_resets = 0
        self._counters_exported: Dict[str, Any] = {}
        if self._state_bytes_per_slot:
            why = ("the adapter's cache holds per-slot recurrent state "
                   f"({self._state_bytes_per_slot} bytes a slot), which "
                   "is a function of every token so far: ")
            if config.prefix_cache:
                raise ValueError(
                    why + "a prefix-cache hit would start a row at "
                    "depth d with no state for d — set "
                    "EngineConfig.prefix_cache=False")
            if config.spec_decode:
                raise ValueError(
                    why + "a rejected draft cannot be rewound out of a "
                    "recurrence — set EngineConfig.spec_decode=False")
            if not config.ragged_batching or mesh is not None:
                raise ValueError(
                    why + "only the unsharded ragged step carries it — "
                    "set EngineConfig.ragged_batching=True, no mesh")
        # A model with no paged layer (PagedEngineAdapter.paged_kv) gets
        # no pool: zero pages, block tables of no column, and every
        # request needs zero pages, so admission is by free slots alone.
        self._paged_kv = bool(adapter.paged_kv)
        if not self._paged_kv and not self._state_bytes_per_slot:
            raise ValueError(
                "PagedEngineAdapter.paged_kv=False says the cache is "
                "state by slot only, but state_bytes_per_slot is 0")
        if bool(adapter.state_leaves) != bool(self._state_bytes_per_slot):
            raise ValueError(
                "PagedEngineAdapter.state_leaves names the cache's leaves "
                "held by slot: some exactly where state_bytes_per_slot "
                f"is not 0 (got {adapter.state_leaves!r} with "
                f"{self._state_bytes_per_slot} bytes a slot)")
        page = config.page_size
        self._maxp = (-(-config.max_seq_len // page)
                      if self._paged_kv else 0)
        self._num_pages = ((config.num_pages
                            or config.max_slots * self._maxp)
                           if self._paged_kv else 0)
        with tracing.span("llm.init_cache", startup=True):
            if mesh is not None and adapter.cache_shardings is not None:
                # Allocate the pool directly under its shardings: a
                # materialize-then-reshard would briefly hold the WHOLE
                # unsharded pool on one device — an OOM at exactly the
                # model sizes tp serving exists for.
                self._cache = jax.jit(
                    partial(adapter.init_cache, self._num_pages, page),
                    out_shardings=adapter.cache_shardings(mesh),
                )()
            elif self._state_bytes_per_slot:
                self._cache = adapter.init_cache(self._num_pages, page,
                                                 config.max_slots)
            else:
                self._cache = adapter.init_cache(self._num_pages, page)
            jax.block_until_ready(self._cache)
        # The cache's two parts in bytes, from the tree itself: what it
        # holds by slot (the leaves the adapter names) and the page
        # pools with their scales (every other leaf).
        parts = ({k: int(v.size * v.dtype.itemsize)
                  for k, v in self._cache.items()
                  if k not in adapter.counter_leaves}
                 if isinstance(self._cache, dict) else {})
        self._state_cache_bytes = sum(
            parts[k] for k in adapter.state_leaves)
        self._paged_kv_bytes = (sum(parts.values())
                                - self._state_cache_bytes)
        if (isinstance(self._cache, dict)
                and "k_scale" in self._cache
                and config.prefill_chunk > 0
                and not config.ragged_batching):
            # The ragged path appends through a page-granular
            # one-hot gather that CAN grow page scales, so int8 KV
            # + chunked prompts is only a restriction of the legacy
            # interleave.
            raise ValueError(
                "kv_int8 pools do not support chunked prefill "
                "(per-token page scatters cannot grow page scales "
                "on the gather path) — set "
                "EngineConfig.prefill_chunk=0, enable "
                "ragged_batching, or serve with bf16 KV")
        self._free_pages = list(range(self._num_pages))
        self._slot_pages: Dict[int, List[int]] = {}
        # Unallocated block-table entries hold the OOB sentinel
        # (num_pages): a stale slot decoded past its allocation by
        # an overshooting in-flight chunk then scatters out of
        # bounds (mode="drop") instead of corrupting page 0.
        self._bt = np.full((config.max_slots, self._maxp),
                           self._num_pages, np.int32)
        self._lens = np.zeros((config.max_slots,), np.int32)
        self._backlog: List[Request] = []  # admitted-but-no-pages
        # Radix-tree prefix cache (EngineConfig.prefix_cache):
        # finished requests donate full pages to the trie; slots
        # borrow them at admission (_slot_borrowed tracks which
        # block-table entries are cache-owned so release never
        # returns them to the free list).
        self._prefix = None
        self._slot_borrowed: Dict[int, List[int]] = {}
        if config.prefix_cache:
            if not config.ragged_batching:
                raise ValueError(
                    "prefix_cache requires ragged_batching=True "
                    "(prefill-from-offset rides the ragged step's "
                    "per-row start descriptor)")
            from ray_tpu.serve.prefix_index import PrefixIndex
            self._prefix = PrefixIndex(page)
        self._prefix_hit_tokens = 0
        self._prefix_prompt_tokens = 0
        # KV page-migration plane (serve/kv_transfer): clients enqueue
        # lease/export/ingest ops here and the LOOP thread services
        # them (_process_migrations) — the cache is donated between
        # jitted dispatches, so only the loop may touch it.
        self._mig_lock = threading.Lock()
        self._mig_ops: List[Dict[str, Any]] = []
        self._mig_leases: Dict[str, Dict[str, Any]] = {}
        self._mig_lease_ids = itertools.count(1)
        self._mig_counts = {"pages_out": 0, "pages_in": 0,
                            "bytes_out": 0, "bytes_in": 0}
        self._waiting: "queue.Queue[Request]" = queue.Queue()
        self._slot_req: Dict[int, Request] = {}
        self._free_slots = list(range(config.max_slots))
        # Last sampled token per slot lives ON DEVICE: the next decode
        # chunk reads it without a host round trip, which is what lets
        # chunk N+1 dispatch before chunk N's tokens reach the host
        # (the depth-2 dispatch pipeline; what it hides on a directly
        # attached chip is not measured — ROADMAP Queue 1 items 2-3).
        self._cur_dev = jnp.zeros((config.max_slots,), jnp.int32)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._cur_dev = jax.device_put(
                self._cur_dev, NamedSharding(mesh, PartitionSpec()))
        self._temps = np.zeros((config.max_slots,), np.float32)
        # In-flight entries (prefill/decode) ride a dedicated FETCH
        # thread: the engine loop dispatches device work and emits
        # fetched tokens, while the fetcher turns queued entries into
        # ONE batched device_get at a time (a get costs a host sync
        # regardless of payload, so the batch size self-balances to the
        # arrival rate).
        self._fetchq: "queue.Queue" = queue.Queue()
        self._fetched: "queue.Queue" = queue.Queue()
        self._unprocessed = 0  # dispatched entries not yet emitted
        self._emit_seq = 0     # step of the fetched entry being emitted
        self._inflight_tokens: Dict[int, int] = {}  # slot → undelivered
        self._req_counter = itertools.count()
        # Cumulative arrival count (every submit, shed included) —
        # mirrored by the arrived counter; kept as a plain int so
        # pressure() reads it without touching the registry.
        self._arrived = 0
        self._stopped = threading.Event()
        # Preemption-aware drain (see drain()): _draining stops
        # admission, _drain_evict tells the loop to preempt whatever is
        # still in flight.  Both are one-way latches.
        self._draining = threading.Event()
        self._drain_evict = threading.Event()
        self._preempted_count = 0
        self._work = threading.Event()
        self._steps = 0
        self._tokens_out = 0
        self._tm = _telemetry()
        # Multi-host shard groups: per-step collective byte accounting
        # + one-time timed calibration probes (see PagedEngineAdapter).
        self._coll_bytes_fn = None
        if (mesh is not None
                and adapter.collective_step_bytes is not None):
            self._coll_bytes_fn = partial(
                adapter.collective_step_bytes, mesh)
        if mesh is not None and adapter.collective_probes is not None:
            self._calibrate_collectives(adapter.collective_probes(mesh))
        self._update_page_gauges()
        self._tm["state_cache_bytes"].set(self._state_cache_bytes)
        # Request-lifecycle ring (util/state.list_requests, dashboard
        # /api/v0/requests, timeline request rows all read it).  The
        # engine holds the only strong ref; the module registry is weak.
        self._engine_id = f"engine-{next(_ENGINE_IDS)}"
        self._ring = _reqev.RequestEventBuffer(self._engine_id)
        _reqev.register(self._ring)
        # Invariant audit plane (serve/audit + util/doctor): the
        # auditor runs O(slots) conservation checks between dispatches
        # and full partition walks on demand / idle / drain / stop.
        # doctor() enqueues audit ops exactly like the cancel and
        # migration queues — the loop owns all audited state.
        self._auditor = _audit.EngineAuditor(self)
        self._audit_lock = threading.Lock()
        self._audit_ops: List[Dict[str, Any]] = []
        self._crashed = False
        self._drain_audited = False
        _audit.register_engine(self)
        # Cancellation handoff: client threads drop ids here; the
        # engine loop resolves them against its registries between
        # dispatches (the loop owns all slot/page state).
        self._cancel_lock = threading.Lock()
        self._cancels: set = set()
        # Goodput accounting: tokens from SLO-met requests vs all
        # tokens of terminal requests.
        self._good_tokens = 0
        self._terminal_tokens = 0
        # Where the loop's wall time goes, and the pace of its steps
        # (serve/loop_clock): the high-water mark of the step interval
        # is mirrored to the gauge, a stall is named and recorded.
        self._clock = LoopClock(on_stall=self._on_loop_stall,
                                on_high_water=self._tm["step_wall"].set)
        self._stall_trigger_at = float("-inf")
        self._xprof_recorded: set = set()  # programs already registered

        slots = config.max_slots

        # NOTE on host↔device traffic: every sync is a host round trip
        # and even jax.random.split is a dispatched program — so every
        # per-chunk side op here is folded INTO the jitted programs
        # (keys derive from an int seed inside jit; the next-token
        # vector and the updated cur come back as extra outputs), and
        # token fetches are deferred + batched.

        @_program("serve.prefill", static_argnums=(0,),
                  donate_argnums=(2,))
        def prefill_batch_fn(k, params, cache, tokens, true_lens,
                             pages_rows, temps, seed, cur, slot_ids):
            """Prefill k slots in ONE dispatch (k static: {1,2,4,8}).
            Rows are sequential inside the program (each writes its own
            slot); padding rows are copies of the last real row — an
            idempotent rewrite whose sample is discarded.  Also scatters
            the sampled first tokens into the device-resident cur."""
            keys = jax.random.split(jax.random.key(seed[0]), k)

            def body(i, carry):
                cache, toks = carry
                logits, cache = adapter.prefill_slot(
                    params, tokens[i], true_lens[i], pages_rows[i], cache
                )
                tok = _sample(logits[None, :], temps[i][None], keys[i])[0]
                return cache, toks.at[i].set(tok)

            cache, toks = jax.lax.fori_loop(
                0, k, body, (cache, jnp.zeros((k,), jnp.int32))
            )
            # Padding rows carry an OOB scatter id (mode="drop"): with
            # temperature > 0 they sample a DIFFERENT token for the
            # same slot, and the scatter must not let a padding row's
            # sample beat the emitted real-row token.
            return cache, toks, cur.at[slot_ids].set(toks, mode="drop")

        @_program("serve.decode", static_argnums=(0,),
                  donate_argnums=(2,))
        def decode_paged_fn(n_steps, params, cache, cur, active, temps,
                            seed, bt, lens):
            def step(carry, k):
                cache, cur, lens = carry
                logits, cache, lens = adapter.decode_slots(
                    params, cur, active, bt, lens, cache
                )
                toks = _sample(logits, temps, k)
                toks = jnp.where(active, toks, cur)
                return (cache, toks, lens), toks

            keys = jax.random.split(jax.random.key(seed[0]), n_steps)
            (cache, cur, lens), toks = jax.lax.scan(
                step, (cache, cur, lens), keys
            )
            # cur + lens ride back as DEVICE arrays: the next dispatch
            # feeds them straight in — no host round trip.
            return cache, toks, cur, lens

        if adapter.prefill_chunk is not None:
            @_program("serve.prefill_chunk", donate_argnums=(1,))
            def prefill_chunk_fn(params, cache, tokens, start, chunk_lens,
                                 pages_rows, temps, seed, cur, slot_ids):
                logits, cache = adapter.prefill_chunk(
                    params, tokens, start, chunk_lens, pages_rows, cache
                )
                toks = _sample(logits, temps, jax.random.key(seed[0]))
                return cache, toks, cur.at[slot_ids].set(toks,
                                                         mode="drop")

            self._prefill_chunk_fn = prefill_chunk_fn
        else:
            self._prefill_chunk_fn = None
        # Ragged batching: ONE jitted program per scheduler step, fed a
        # packed token buffer of decode rows + prefill chunks.  Static
        # (T, R): R = max_slots, and T is the smallest of
        # ragged_step_shapes that holds the step's tokens, so jit keeps
        # two executables of the one function and a step without a
        # prompt chunk does not run the budget's positions through every
        # matmul.  Only the [T] arrays differ between the two.
        self._ragged = bool(config.ragged_batching)
        self._weight_routes = None
        if self._ragged:
            if adapter.ragged_step is None:
                raise ValueError(
                    "EngineConfig.ragged_batching requires a "
                    "PagedEngineAdapter with ragged_step")
            if mesh is not None:
                raise ValueError(
                    "ragged_batching does not support mesh-sharded "
                    "serving yet — drop mesh= or ragged_batching")
            self._token_budget = config.token_budget or (
                config.max_slots
                + max(config.prefill_chunk, config.page_size))
            if self._token_budget < config.max_slots + 1:
                raise ValueError(
                    "token_budget must leave room for a prefill chunk "
                    f"beside {config.max_slots} decode rows")
            if (adapter.max_row_tokens is not None
                    and self._token_budget > adapter.max_row_tokens):
                raise ValueError(
                    f"a row may take the whole token budget "
                    f"({self._token_budget}: token_budget, or max_slots + "
                    f"prefill_chunk), and the adapter's step takes rows of "
                    f"at most {adapter.max_row_tokens} fresh tokens "
                    f"(PagedEngineAdapter.max_row_tokens) — set a smaller "
                    f"EngineConfig.prefill_chunk or token_budget")
            self._ragged_shapes = ragged_step_shapes(
                self._token_budget, config.max_slots)
            self._steps_by_shape = {T: 0 for T in self._ragged_shapes}

            self._ragged_step_fn = self._ragged_program("serve.ragged")
            if self._state_bytes_per_slot:
                from ray_tpu.util import flight_recorder
                flight_recorder.record(
                    "serve_cache_parts", engine=self._engine_id,
                    paged_kv_bytes=self._paged_kv_bytes,
                    recurrent_state_bytes=self._state_cache_bytes,
                    slots=config.max_slots, pages=self._num_pages,
                    state_bytes_per_slot=self._state_bytes_per_slot)
                log.info(
                    "serve.ragged cache: recurrent state for %d slots "
                    "(%d bytes a slot, %d in all) and %s; prefix cache, "
                    "speculation and KV migration are refused",
                    config.max_slots, self._state_bytes_per_slot,
                    self._state_cache_bytes,
                    f"{self._num_pages} KV pages "
                    f"({self._paged_kv_bytes} bytes)"
                    if self._paged_kv else "no KV page")
            self._weight_routes = (adapter.weight_routes(params)
                                   if adapter.weight_routes else None)
            if self._weight_routes is not None:
                from ray_tpu.util import flight_recorder
                flight_recorder.record("ragged_weight_routes",
                                       engine=self._engine_id,
                                       **self._weight_routes)
                log.info(
                    "serve.ragged layer kernel reads in place: %s; "
                    "sliced (copied) per layer and step: %s",
                    ", ".join(self._weight_routes["in_place"]),
                    ", ".join(self._weight_routes["sliced"]))

            # Multi-tenant LoRA multiplexing: the engine owns the paged
            # adapter pool; a step that carries adapter rows hands the
            # pool, its gather plan and the per-token adapter index to
            # the same program as lora= (a second trace of it).  The
            # pool array is NOT donated — the host manager mutates it
            # on loads, not the step.  Batches with no adapter rows
            # keep the base trace, so adapter-off traffic pays zero
            # overhead.
            self._adapters = (adapter.make_adapter_pool(config)
                              if adapter.make_adapter_pool else None)

            if self._prefix is not None:
                if adapter.copy_page is None:
                    raise ValueError(
                        "prefix_cache requires an adapter with "
                        "copy_page (the COW split of a shared page)")

                @_program("serve.copy_page", donate_argnums=(0,))
                def copy_page_fn(cache, src, dst):
                    return adapter.copy_page(cache, src, dst)

                self._copy_page_fn = copy_page_fn
                # the migration programs below read and write the pool
                # by these names
                pool_leaves = ([] if not isinstance(self._cache, dict)
                               else [k for k in self._cache
                                     if k not in adapter.state_leaves
                                     and k not in adapter.counter_leaves])
                for leaf in pool_leaves:
                    if leaf not in ("k", "v", "k_scale", "v_scale"):
                        raise ValueError(
                            "prefix_cache builds the KV migration "
                            "programs, which ship the page pools "
                            "\"k\"/\"v\" (and their scales) by name; this "
                            f"adapter's pool has the leaf {leaf!r} — set "
                            "EngineConfig.prefix_cache=False")

                # Migration gather/scatter (serve/kv_transfer).  Page
                # ids are padded to a power of two (fill = the OOB
                # scratch page) to bound recompiles; the gather's
                # padding rows are sliced off on the host, the
                # scatter's padding rows write zeros into the scratch
                # page, where nothing can read them.
                @_program("serve.mig_gather")
                def mig_gather_fn(cache, ids):
                    out = {"k": cache["k"][:, :, ids],
                           "v": cache["v"][:, :, ids]}
                    if "k_scale" in cache:
                        out["k_scale"] = cache["k_scale"][:, ids]
                        out["v_scale"] = cache["v_scale"][:, ids]
                    return out

                @_program("serve.mig_scatter", donate_argnums=(0,))
                def mig_scatter_fn(cache, ids, payload):
                    out = dict(cache)
                    for key in ("k", "v"):
                        out[key] = cache[key].at[:, :, ids].set(
                            payload[key])
                    for key in ("k_scale", "v_scale"):
                        if key in cache:
                            out[key] = cache[key].at[:, ids].set(
                                payload[key])
                    return out

                self._mig_gather_fn = mig_gather_fn
                self._mig_scatter_fn = mig_scatter_fn
            if config.spec_decode:
                if "logit_idx" not in inspect.signature(
                        adapter.ragged_step).parameters:
                    raise ValueError(
                        "EngineConfig.spec_decode needs a ragged_step "
                        "that takes logit_idx= (the verify rows' extra "
                        "logits); this adapter's does not — set "
                        "spec_decode=False")
                self._init_spec(draft_params, draft_adapter)
        else:
            if config.spec_decode:
                raise ValueError(
                    "EngineConfig.spec_decode requires "
                    "ragged_batching=True — verify rows are k-token "
                    "prefill-chunk rows of the unified ragged step")
            if adapter.make_adapter_pool is not None:
                raise ValueError(
                    "LoRA multiplexing requires ragged_batching — the "
                    "segmented adapter matmul rides the unified step")
            for need in ("prefill_slot", "decode_slots"):
                if getattr(adapter, need) is None:
                    raise ValueError(
                        "EngineConfig.ragged_batching=False serves "
                        "through the two-program path, which needs "
                        f"PagedEngineAdapter.{need}; this adapter does "
                        "not provide it — set ragged_batching=True")
            self._adapters = None
            self._ragged_step_fn = None
            self._token_budget = 0
            self._steps_by_shape = {}
        # Adapter borrow per slot ("" = base model): released with the
        # slot on every terminal path.
        self._slot_adapter: Dict[int, str] = {}
        # Requests mid-incremental-prefill: [{req, slot, pos}].
        self._prefilling: List[Dict[str, Any]] = []
        # Requests whose admission prefill is being dispatched — a
        # crash mid-dispatch must fail them (they are in no other
        # registry yet).
        self._admitting: List[Request] = []

        if adapter.prefill_batch is not None:
            @_program("serve.prefill", donate_argnums=(1,))
            def prefill_batched_fn(params, cache, tokens, true_lens,
                                   pages_rows, temps, seed, cur,
                                   slot_ids):
                logits, cache = adapter.prefill_batch(
                    params, tokens, true_lens, pages_rows, cache
                )
                toks = _sample(logits, temps, jax.random.key(seed[0]))
                # Padding rows' scatter ids are OOB — see prefill_batch_fn.
                return cache, toks, cur.at[slot_ids].set(toks, mode="drop")

            self._prefill_batched_fn = prefill_batched_fn
        else:
            self._prefill_batched_fn = None
        self._prefill_batch_fn = prefill_batch_fn
        self._decode_fn = decode_paged_fn
        self._seed_counter = itertools.count(seed * 1_000_003 + 1)
        # Decode chunk ladder: descending powers of two (see
        # _chunk_size).
        ladder = []
        k = max(1, config.decode_chunk)
        while k >= 1:
            ladder.append(k)
            k //= 2
        self._chunk_ladder = tuple(ladder)
        # Per-slot control arrays riding dispatches as jit args,
        # rebuilt only when admission/finish dirties them.
        self._state_dirty = True
        self._active_arg = None
        self._temps_arg = None
        self._bt_arg = None
        self._lens_arg = None
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="llm-engine"
        )
        self._thread.start()
        self._fetcher = threading.Thread(
            target=self._fetch_loop, daemon=True, name="llm-fetch"
        )
        self._fetcher.start()

    def _init_spec(self, draft_params: Any,
                   draft_adapter: Optional[PagedEngineAdapter]) -> None:
        """Build the speculative-decoding plane: a second small paged
        pool for the draft model's KV (same allocator discipline, own
        OOB scratch page), the draft feed/chain programs, and the
        target verify program — the ragged step returning EXTRA logits
        at each verify row's candidate positions.  The BASE ragged
        program is untouched: batches without verify rows keep
        dispatching it, so spec-off output is the byte-identical oracle
        by construction."""
        config, adapter = self.config, self.adapter
        da = draft_adapter if draft_params is not None else None
        if draft_params is None:
            # Self-draft: draft == target weights.  Every draft is
            # accepted, so this exercises/measures the verify path
            # (and drives the deterministic parity tests) rather than
            # saving device steps.
            draft_params = self._params
        da = da or adapter
        if da.ragged_step is None:
            raise ValueError(
                "spec_decode draft adapter must provide ragged_step")
        page = config.page_size
        R, Td = config.max_slots, self._token_budget
        self._draft_params = draft_params
        self._draft_pages = (config.spec_draft_pages
                             or config.max_slots * self._maxp)
        self._draft_cache = da.init_cache(self._draft_pages, page)
        self._draft_free = list(range(self._draft_pages))
        self._draft_slot_pages: Dict[int, List[int]] = {}
        self._draft_bt = np.full((R, self._maxp), self._draft_pages,
                                 np.int32)
        # Tokens of each slot's sequence already fed to the draft KV.
        self._draft_fed: Dict[int, int] = {}
        # A slot with a verify round in flight is fully idle (its
        # length mirror only advances at the accept boundary, host-side
        # at fetch); a slot whose device cur went stale after a verify
        # round re-seeds it through a host-token decode row.
        self._spec_inflight: set = set()
        self._spec_stale_cur: set = set()
        self._spec_ema = 1.0
        self._spec_cooldown = 0
        self._spec_rounds = 0
        self._spec_drafted_total = 0
        self._spec_accepted_total = 0
        self._spec_cooldowns = 0
        # Static width of the verify-logit gather: flat-buffer indices
        # of every verify row's k+1 candidate tokens, padded with 0.
        self._spec_tv = min(Td, R * (config.spec_k + 1))

        @_program("serve.spec_draft", donate_argnums=(1,))
        def draft_feed_fn(params, cache, host_toks, tok_pos, row_slot,
                          row_start, row_len, row_off, bt):
            logits, cache = da.ragged_step(
                params, host_toks, tok_pos, row_slot, row_start,
                row_len, row_off, bt, cache)
            # Row logits sit at each row's LAST fed token: a row fed
            # through its sequence end yields draft token 1 directly.
            return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        @_program("serve.spec_chain", donate_argnums=(1,))
        def draft_chain_fn(params, cache, prev, tok_pos, row_slot,
                           row_start, row_len, row_off, bt):
            # One-token rows at row_off = arange(R): the previous
            # step's [R] argmax IS the head of the flat token buffer.
            toks = jnp.zeros((Td,), jnp.int32).at[:R].set(prev)
            logits, cache = da.ragged_step(
                params, toks, tok_pos, row_slot, row_start, row_len,
                row_off, bt, cache)
            return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        self._draft_feed_fn = draft_feed_fn
        self._draft_chain_fn = draft_chain_fn

        self._ragged_step_spec_fn = self._ragged_program(
            "serve.ragged_spec")
        self._spec_on = True

    def _ragged_program(self, name: str):
        """The jitted unified step under its registered name:
        ``serve.ragged``, and ``serve.ragged_spec`` for steps with
        speculative verify rows (always called with ``logit_idx``).  A
        step with adapter rows passes ``lora``; None is an empty
        argument, so the step without it traces to the base program."""
        step = self.adapter.ragged_step

        @_program(name, donate_argnums=(1,))
        def ragged_step_fn(params, cache, host_toks, decode_mask,
                           tok_slot, tok_pos, row_slot, row_start,
                           row_len, row_off, temps, seed, cur,
                           scatter_ids, bt, lora=None, logit_idx=None):
            # Decode rows read their token from the device-resident
            # cur (no host round trip — same pipelining contract as
            # decode_paged_fn); prefill rows carry host tokens.
            toks = jnp.where(decode_mask, cur[tok_slot], host_toks)
            kw = {k: v for k, v in (("lora", lora),
                                    ("logit_idx", logit_idx))
                  if v is not None}
            logits, *vlogits, cache = step(
                params, toks, tok_pos, row_slot, row_start, row_len,
                row_off, bt, cache, **kw)
            out = sampled = _sample(logits, temps,
                                    jax.random.key(seed[0]))
            if vlogits:
                # Per-position target argmax of every verify candidate,
                # computed on device — the fetch carries k+1 ints per
                # verify row instead of k+1 logit vectors.
                out = (sampled, jnp.argmax(
                    vlogits[0], axis=-1).astype(jnp.int32))
            # Mid-chunk prefill rows, padding rows and verify rows
            # carry OOB scatter ids: their sample is meaningless (a
            # verify row's accept boundary decides its token) and must
            # not clobber a live slot's cur.
            cur = cur.at[scatter_ids].set(sampled, mode="drop")
            return cache, out, cur

        return ragged_step_fn

    # -- client API --------------------------------------------------------

    def submit(self, prompt: List[int], *, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               request_id: Optional[str] = None,
               adapter_id: str = "") -> CompletionStream:
        # On the request's own thread, from the call to the request
        # queued: a capture shows the span inside that thread's
        # ``serve.replica``.
        with tracing.span("llm.submit", record=False):
            return self._submit(prompt, max_new_tokens, temperature,
                                request_id, adapter_id)

    def _submit(self, prompt: List[int], max_new_tokens: Optional[int],
                temperature: float, request_id: Optional[str],
                adapter_id: str) -> CompletionStream:
        if self._stopped.is_set():
            raise RuntimeError("engine is stopped (shut down or crashed)")
        if self._draining.is_set():
            # Uniform failover signal: the router resubmits elsewhere
            # exactly like a mid-stream preemption, with an empty
            # generated prefix.
            raise PreemptedError(
                "engine is draining: not admitting new requests",
                continuation={"prompt": list(prompt), "tokens": [],
                              "temperature": float(temperature),
                              "request_id": request_id or "",
                              "adapter_id": adapter_id})
        # Count the arrival before any admission decision: the signal
        # must see offered load, not just what survived shedding.
        self._arrived += 1
        self._tm["arrived"].inc()
        shed_after = self.config.shed_queue_age_s
        if shed_after is not None:
            age = self._admission_queue_age()
            if age > shed_after:
                # Admission control: a request queued now waits behind
                # work that is ALREADY over the SLO budget.  Record the
                # SHED terminal (no attempt ever runs, so this is the
                # request's whole story in this engine's ring) and fail
                # fast — goodput accounting is untouched: shed requests
                # produced zero tokens and protect the admitted ones.
                rid = (request_id or _reqev.get_request_id()
                       or f"{self._engine_id}-r{next(self._req_counter)}")
                self._ring.record(rid, _reqev.SHED,
                                  prompt_tokens=len(prompt),
                                  terminal_cause="ShedError",
                                  adapter_id=adapter_id)
                self._tm["shed"].inc()
                self._tm["terminal"].inc(tags={"state": _reqev.SHED})
                try:
                    from ray_tpu.util import flight_recorder
                    flight_recorder.trigger("shed", request_id=rid,
                                            queue_age_s=age)
                except Exception:
                    pass
                raise ShedError(queue_age_s=age)
        if adapter_id and self._adapters is None:
            raise ValueError(
                f"request carries adapter_id {adapter_id!r} but this "
                "engine has no adapter pool (model config without "
                "lora=, or non-ragged serving)")
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) >= self.config.max_seq_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= max_seq_len "
                f"{self.config.max_seq_len}"
            )
        req = Request(
            prompt=list(prompt),
            max_new_tokens=max_new_tokens or self.config.max_new_tokens_default,
            temperature=float(temperature),
            stream=queue.Queue(),
            req_id=next(self._req_counter),
            trace_ctx=(tracing.capture_context()
                       if tracing.is_enabled() else None),
            adapter_id=adapter_id,
        )
        # Explicit id > the ambient one the serve replica installed
        # (router-minted, riding request metadata) > local mint.
        req.request_id = (request_id or _reqev.get_request_id()
                          or f"{self._engine_id}-r{req.req_id}")
        # Reject requests the page pool can NEVER satisfy — they
        # would otherwise wedge admission head-of-line forever.
        need = self._pages_needed(req)
        if need > self._num_pages:
            raise ValueError(
                f"request needs {need} pages "
                f"({len(prompt)}+{req.max_new_tokens} tokens, page "
                f"{self.config.page_size}) but the pool has only "
                f"{self._num_pages}"
            )
        self._ring.record(req.request_id, _reqev.QUEUED,
                          prompt_tokens=len(req.prompt),
                          adapter_id=req.adapter_id)
        log.debug("request %s queued (%d prompt tokens, max_new=%d)",
                  req.request_id, len(req.prompt), req.max_new_tokens)
        self._waiting.put(req)
        self._work.set()
        return CompletionStream(req, self)

    def cancel(self, request_id: str) -> None:
        """Cancel a request by id.  Idempotent; unknown or already
        terminal ids are a no-op.  Resolution happens on the engine
        loop (which owns slot/page state): the request reaches
        CANCELLED, its slot and pages are released, and its stream ends
        normally with the tokens generated so far."""
        if self._stopped.is_set():
            return
        with self._cancel_lock:
            self._cancels.add(request_id)
        self._work.set()

    def drain(self, grace_s: float = 5.0) -> int:
        """Preemption-aware drain: stop admitting, give requests
        already in a slot ``grace_s`` to finish, then evict the
        survivors with a PREEMPTED terminal whose PreemptedError
        carries the continuation payload (prompt + tokens generated so
        far + sampling state) — everything a surviving replica needs to
        resume with one re-prefill.  Requests that never reached a slot
        are evicted immediately (admission is the thing a drain stops).
        Blocking; callable from any thread; idempotent.  Returns the
        number of requests preempted so far."""
        if self._stopped.is_set():
            return self._preempted_count
        self._draining.set()
        self._work.set()
        deadline = time.monotonic() + max(0.0, grace_s)
        while (time.monotonic() < deadline
               and not self._stopped.is_set()
               and not self._drain_idle()):
            time.sleep(0.01)
        self._drain_evict.set()
        self._work.set()
        # The loop owns slot/page state; give it a bounded window to
        # run the eviction pass.
        evict_deadline = time.monotonic() + 5.0
        while (time.monotonic() < evict_deadline
               and not self._stopped.is_set()
               and not self._drain_idle()):
            time.sleep(0.01)
        return self._preempted_count

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def _drain_idle(self) -> bool:
        """No request the drain still has to account for."""
        if self._slot_req or not self._waiting.empty() or self._admitting:
            return False
        return not (self._prefilling or self._backlog)

    def generate(self, prompt: List[int], **kw) -> List[int]:
        return self.submit(prompt, **kw).result()

    @property
    def engine_id(self) -> str:
        """Stable name of this engine's request ring (the ``engine``
        key on state.list_requests rows)."""
        return self._engine_id

    def stats(self) -> Dict[str, Any]:
        out = {
            "engine": self._engine_id,
            "active_slots": self.config.max_slots - len(self._free_slots),
            "prefilling": len(getattr(self, "_prefilling", ())),
            "waiting": self._waiting.qsize(),
            "steps": self._steps,
            "tokens_out": self._tokens_out,
            "stall_events": self._clock.stall_events,
            "loop": self._clock.snapshot(),
            "steps_by_shape": dict(self._steps_by_shape),
            "requests": self._ring.counts_by_state(),
            # how this process started: seconds by start-up span,
            # ready_s, programs compiled, cache misses
            "startup": xprof.startup_table(),
        }
        out["kv_pages_free"] = len(self._free_pages)
        out["kv_pages_cached"] = (self._prefix.cached_pages
                                  if self._prefix else 0)
        if self._prefix is not None:
            pstats = self._prefix.stats()
            pstats["hit_tokens"] = self._prefix_hit_tokens
            pstats["prompt_tokens"] = self._prefix_prompt_tokens
            out["prefix"] = pstats
            out["kv_migration"] = dict(self._mig_counts)
        if self._adapters is not None:
            out["adapters"] = self._adapters.stats()
        if self._weight_routes is not None:
            out["weight_routes"] = self._weight_routes
        if self._state_bytes_per_slot:
            slots = self.config.max_slots
            out["state_cache"] = {
                "slots": slots,
                "bytes_per_slot": self._state_bytes_per_slot,
                "bytes": self._state_cache_bytes,
                "live": slots - len(self._free_slots),
                "resets": self._state_resets,
            }
        if self.adapter.counter_leaves:
            out["model_counters"] = self._model_counters()
        if self._spec_on:
            out["spec"] = {
                "rounds": self._spec_rounds,
                "drafted_tokens": self._spec_drafted_total,
                "accepted_tokens": self._spec_accepted_total,
                "accept_ratio": (
                    self._spec_accepted_total / self._spec_drafted_total
                    if self._spec_drafted_total else None),
                "cooldowns": self._spec_cooldowns,
                "ema": self._spec_ema,
                "k": self.config.spec_k,
                "draft_pages_free": len(self._draft_free),
            }
        return out

    def _model_counters(self) -> Dict[str, Any]:
        """The cache's counter leaves (PagedEngineAdapter.counter_leaves)
        with the step whose end they show (``step``), handed out by the
        loop thread (``read_cache``): a few hundred bytes, only when
        asked.  A leaf the engine has a counter for feeds it by what it
        grew."""
        leaves = self.adapter.counter_leaves
        step, got = self.read_cache(
            lambda cache: {k: jnp.copy(cache[k]) for k in leaves})
        for name, now in got.items():
            if name not in self._tm:
                continue
            grew = now - self._counters_exported.get(name, 0)
            self._counters_exported[name] = now
            for layer, expert in zip(*np.nonzero(grew)):
                self._tm[name].inc(
                    int(grew[layer, expert]),
                    tags={"layer": str(layer), "expert": str(expert)})
        return {"step": step, **{k: v.tolist() for k, v in got.items()}}

    def admission_queue_age(self) -> float:
        """Public face of the admission-queue-age gauge: seconds the
        oldest still-unadmitted request has waited (0.0 when nothing
        waits).  The leading overload signal — it climbs before any
        latency SLO blows — pushed to the controller for SLO-pressure
        autoscaling."""
        return self._admission_queue_age()

    def goodput_ratio(self) -> Optional[float]:
        """Cumulative goodput ratio (tokens from SLO-met requests over
        all terminal tokens — the raytpu_serve_goodput_ratio gauge),
        or None before any request reached a terminal state."""
        if not self._terminal_tokens:
            return None
        return self._good_tokens / self._terminal_tokens

    def arrivals_total(self) -> int:
        """Cumulative requests submitted (shed included) — the
        arrival process the predictive autoscaler takes a slope of."""
        return self._arrived

    def prefix_summary(self, max_entries: int = 256) -> Optional[dict]:
        """Compact routing summary of the prefix cache ({"page": …,
        "hashes": [chained CRC32 path hashes]}), or None when the
        cache is off.  Replicas push it to the controller, which
        re-broadcasts it on the route table so routers can prefer the
        replica holding the longest cached prefix."""
        if self._prefix is None:
            return None
        return self._prefix.summary(max_entries)

    def adapter_summary(self) -> Optional[dict]:
        """Compact routing summary of the adapter pool ({"adapters":
        [resident ids]}), or None when LoRA multiplexing is off.
        Published over the controller broadcast table exactly like
        prefix_summary, feeding the router's adapter-affinity arm."""
        if self._adapters is None:
            return None
        return self._adapters.summary()

    def doctor(self, deep: bool = True,
               timeout_s: float = 30.0) -> Dict[str, Any]:
        """Run one invariant audit pass (serve/audit) and return its
        report.  While the loop runs, the audit is enqueued for IT to
        execute between jitted dispatches (the loop owns every audited
        registry — same ownership rule as cancel and migration ops);
        once the engine is stopped the audit runs inline, because no
        mutator is left.  ``deep=False`` runs only the O(slots)
        conservation tier."""
        return self._on_loop(lambda: self._auditor.run(deep=deep),
                             timeout_s, "doctor audit")

    def _on_loop(self, fn: Callable[[], Any], timeout_s: float,
                 what: str) -> Any:
        """``fn()`` on the loop thread, between two dispatches (the loop
        owns the slot and page registries and the cache tree it donates
        to every step); inline once the engine is stopped, because no
        mutator is left."""
        if threading.current_thread() is self._thread:
            return fn()
        if self._stopped.is_set() or not self._thread.is_alive():
            # Let a stopping loop finish its final-audit/cleanup pass
            # first so the inline call never races it.
            self._thread.join(timeout=5.0)
            return fn()
        op: Dict[str, Any] = {"fn": fn, "done": threading.Event(),
                              "result": None, "error": None}
        with self._audit_lock:
            self._audit_ops.append(op)
        self._work.set()
        if not op["done"].wait(timeout_s):
            with self._audit_lock:
                try:
                    self._audit_ops.remove(op)
                except ValueError:
                    pass
            if not op["done"].is_set():
                if self._stopped.is_set():
                    self._thread.join(timeout=5.0)
                    return fn()
                raise TimeoutError(
                    f"{what} not serviced within {timeout_s}s")
        if op["error"] is not None:
            raise op["error"]
        return op["result"]

    def read_cache(self, fn: Callable[[Any], Any],
                   timeout_s: float = 30.0) -> Tuple[int, Any]:
        """``(step, fn(cache))`` as host arrays: what the cache tree
        holds once step ``step`` (``stats()["steps"]``'s count) has run.
        ``fn`` runs on the loop thread between two dispatches and must
        return NEW device arrays (a slice, a copy, a reduction), never a
        leaf itself: the next step donates the tree.  The transfer to
        the host waits on the caller's thread, not the loop's."""
        step, out = self._on_loop(
            lambda: (self._steps, fn(self._cache)), timeout_s, "cache read")
        return step, jax.device_get(out)

    def doctor_report(self) -> Optional[Dict[str, Any]]:
        """The most recent audit report without running a new pass
        (None before the first audit)."""
        return self._auditor.last_report

    def shutdown(self):
        self._stopped.set()
        self._work.set()
        self._fetchq.put(None)  # release the fetcher

    # -- engine loop -------------------------------------------------------

    def _next_seed(self) -> np.ndarray:
        """Per-dispatch RNG seed as a tiny host array — the key derives
        INSIDE the jitted program (jax.random.split on the host is a
        dispatched program of its own)."""
        return np.asarray([next(self._seed_counter) & 0x7FFFFFFF],
                          np.uint32)

    def _admit(self):
        if self._draining.is_set():
            return  # racing submits are preempted, never admitted
        if self._ragged:
            return self._admit_ragged()
        return self._admit_paged()

    def _scatter_ids(self, slot_ids: np.ndarray, n_real: int) -> np.ndarray:
        """cur-scatter indices: real rows keep their slot, padding rows
        go OOB so their (differently-sampled) token is dropped."""
        out = np.array(slot_ids, np.int32)
        out[n_real:] = self.config.max_slots
        return out

    def _instrumented_dispatch(self, name, fn, args, span_name,
                               steps_attr=None, cost_steps=None,
                               shape=None):
        """Dispatch one jitted program; the FIRST dispatch of each
        named program, at each ``shape`` it is compiled for, goes
        through ``xprof.first_call``: the device plane gets its cost
        and its compile window, the start-up record the span
        ``llm.first_step{program, shape}``, and the step's own span
        name one record tagged ``compile=true``, which the roofline
        join and the victim request's waterfall skip.  Later dispatches
        pass straight through.  ``cost_steps`` declares how many tokens
        the recorded cost covers (the per-token denominator for
        waterfall device estimates).  The device plane names a
        program's largest shape by the program's name and any other
        ``<name>@<shape>``: each is an executable with a cost and a
        compile window of its own."""
        if (name, shape) in self._xprof_recorded:
            return fn(*args)
        self._xprof_recorded.add((name, shape))
        if shape is not None and shape != self._token_budget:
            name = f"{name}@{shape}"
        return xprof.first_call(
            name, fn, args, span_name=span_name, steps_attr=steps_attr,
            cost_steps=cost_steps, tag_compile=True,
            **({} if shape is None else {"shape": shape}))

    def _run_prefill(self, k, tokens, true_lens, pages_rows, temps,
                     slot_ids):
        """One admission dispatch: batched [K, S] forward when the
        adapter provides it, else the fori_loop-of-rows program.  The
        sampled first tokens scatter into the device cur INSIDE the
        program; host arrays ride the dispatch (no separate uploads).
        Callers set self._admitting first: a crash inside the dispatch
        must still fail these not-yet-registered requests."""
        # Padding rows are real device work, so they count as
        # dispatched prefill tokens (phase attribution, not goodput).
        self._tm["step_tokens"].inc(int(np.sum(true_lens)),
                                    tags={"phase": "prefill"})
        if self._prefill_batched_fn is not None:
            self._cache, toks_dev, self._cur_dev = \
                self._instrumented_dispatch(
                    "serve.prefill", self._prefill_batched_fn,
                    (self._params, self._cache, tokens, true_lens,
                     pages_rows, temps, self._next_seed(),
                     self._cur_dev, slot_ids),
                    span_name="llm.prefill",
                    cost_steps=float(np.sum(true_lens)),
                )
        else:
            self._cache, toks_dev, self._cur_dev = \
                self._instrumented_dispatch(
                    "serve.prefill", self._prefill_batch_fn,
                    (k, self._params, self._cache, tokens, true_lens,
                     pages_rows, temps, self._next_seed(),
                     self._cur_dev, slot_ids),
                    span_name="llm.prefill",
                    cost_steps=float(np.sum(true_lens)),
                )
        return toks_dev

    def _finish_admit(self, batch, toks_dev, slot_ids) -> None:
        """Post-prefill bookkeeping of the two-program path.  The
        first-token FETCH is deferred into the pipeline (one batched
        device_get covers several entries — each sync get is a host
        round trip); slots register NOW so
        decode chunks dispatch behind the prefill without waiting."""
        now = time.monotonic()
        for req, slot in batch:
            self._slot_req[slot] = req
            self._temps[slot] = req.temperature
            if req.admitted_at is None:
                req.admitted_at = now
            self._ring.record(
                req.request_id, _reqev.PREFILLING, slot=slot,
                num_pages=len(self._slot_pages.get(slot, [])))
            # The pending first token counts against the budget until
            # the prefill entry is processed.
            self._inflight_tokens[slot] = \
                self._inflight_tokens.get(slot, 0) + 1
        # Cleared only AFTER every request is registered: a crash in
        # the window between the two registries would otherwise strand
        # clients (an overlap double-fail is a benign extra put).
        self._admitting = []
        self._state_dirty = True  # active/temps/bt/lens changed
        self._unprocessed += 1
        self._clock.step_dispatched()
        self._fetchq.put(("prefill", toks_dev, 0, list(batch),
                          self._steps))

    def _alloc_slot_pages(self, req: Request,
                          need: Optional[int] = None) -> Optional[int]:
        """Claim a slot + its pages for a request; the block-table row
        gets real pages then the OOB sentinel (see _bt).  None when the
        pool can't cover it."""
        if need is None:
            need = self._pages_needed(req)
        if not self._free_slots:
            return None
        if len(self._free_pages) < need and self._prefix is not None:
            # Admission pressure evicts refcount-0 LRU cache pages
            # BEFORE the request queues: the cache borrows idle pool
            # capacity, it never competes with admission for it.
            freed = self._prefix.evict(need - len(self._free_pages))
            if freed:
                self._free_pages.extend(freed)
                self._tm["prefix_evicted"].inc(len(freed))
        if len(self._free_pages) < need:
            return None
        slot = self._free_slots.pop()
        pages = [self._free_pages.pop() for _ in range(need)]
        self._slot_pages[slot] = pages
        row = np.full((self._maxp,), self._num_pages, np.int32)
        row[: len(pages)] = pages
        self._bt[slot] = row
        self._update_page_gauges()
        return slot

    def _admit_slot_for(self, req: Request) -> Optional[Tuple[int, int]]:
        """Claim a slot + pages, borrowing the longest cached prefix
        when the prefix cache is on.  Returns (slot, start) — the
        ragged prefill resumes at ``start`` instead of 0 — or None
        under slot/page pressure (every borrowed ref released).

        Only FULL pages are cached and prefill resumes at the hit
        boundary, so shared pages are never written — except an exact
        full-prompt hit, where the mandatory last-token re-run (the
        sample needs its logits) lands inside the deepest shared page.
        That page is COW-split into a fresh page before scheduling."""
        if self._prefix is None:
            slot = self._alloc_slot_pages(req)
            return None if slot is None else (slot, 0)
        page = self.config.page_size
        hit_pages = self._prefix.acquire(req.prompt)
        d = len(hit_pages)
        start = hit = d * page
        cow = d > 0 and hit >= len(req.prompt)
        if cow:
            start = len(req.prompt) - 1
        need_total = self._pages_needed(req)
        slot = self._alloc_slot_pages(
            req, need=need_total - d + (1 if cow else 0))
        if slot is None:
            self._prefix.release(hit_pages)
            self._update_page_gauges()
            return None
        fresh = self._slot_pages[slot]
        if cow:
            src, dst = hit_pages[-1], fresh[0]
            self._cache = self._copy_page_fn(
                self._cache, np.int32(src), np.int32(dst))
            self._prefix.release([src])
            borrowed = hit_pages[:-1]
            pages = borrowed + [dst] + fresh[1:]
        else:
            borrowed = hit_pages
            pages = borrowed + fresh
        self._slot_pages[slot] = pages
        self._slot_borrowed[slot] = borrowed
        row = np.full((self._maxp,), self._num_pages, np.int32)
        row[: len(pages)] = pages
        self._bt[slot] = row
        req.prefix_hit = start
        self._prefix_hit_tokens += start
        self._prefix_prompt_tokens += len(req.prompt)
        self._tm["prefix_requests"].inc(
            tags={"outcome": "hit" if start else "miss"})
        self._tm["prefix_hit_depth"].observe(start)
        if self._prefix_prompt_tokens:
            self._tm["prefix_hit_ratio"].set(
                self._prefix_hit_tokens / self._prefix_prompt_tokens)
        self._update_page_gauges()
        return slot, start

    def _calibrate_collectives(self, probes: Dict[str, Callable]) -> None:
        """Time one decode-shaped collective per populated link class
        and observe raytpu_serve_collective_seconds with MEASURED wall
        time.  Runs once at engine construction: the first call
        compiles (untimed), the next three are timed — honest
        measurement rather than fabricated per-step attribution."""
        for link, probe in sorted(probes.items()):
            probe()  # compile
            for _ in range(3):
                t0 = time.perf_counter()
                probe()
                self._tm["collective_seconds"].observe(
                    time.perf_counter() - t0, tags={"link": link})

    def _count_collective_bytes(self, rows: int, steps: int = 1) -> None:
        """Per-dispatch analytic wire accounting for a decode of
        ``rows`` active slots × ``steps`` device steps."""
        if self._coll_bytes_fn is None or rows <= 0:
            return
        per_step = self._coll_bytes_fn(rows)
        for link, nbytes in per_step.items():
            if nbytes:
                self._tm["collective_bytes"].inc(
                    nbytes * steps, tags={"link": link})

    def _update_page_gauges(self) -> None:
        self._tm["kv_pages_free"].set(len(self._free_pages))
        cached = self._prefix.cached_pages if self._prefix else 0
        self._tm["kv_pages_cached"].set(cached)
        if self._prefix is not None:
            self._tm["prefix_cached_pages"].set(cached)

    def _pages_needed(self, req: Request) -> int:
        """Pages covering max(prefill bucket, prompt+max_new)."""
        page = self.config.page_size
        bucket = self._paged_bucket_for(len(req.prompt))
        return min(max(bucket // page,
                       -(-(len(req.prompt) + req.max_new_tokens) // page)),
                   self._maxp)

    def _paged_bucket_for(self, n: int) -> int:
        """Prefill bucket rounded UP to a page multiple: the paged
        prefill writes whole pages, so a bucket smaller than a page
        would write NO prompt k/v at all."""
        page = self.config.page_size
        for b in self.config.buckets():
            if n <= b:
                return -(-b // page) * page
        raise ValueError(f"prompt length {n} exceeds max bucket")

    def _admit_paged(self):
        """Admission with page allocation: a request needs pages for
        max(prefill bucket, prompt+max_new) tokens; when the pool can't
        cover it the request waits in the backlog (continuous batching
        under page pressure, the PagedAttention admission rule).  Long
        prompts (> prefill_chunk) go to the incremental-prefill track
        instead of a one-shot bucket."""
        page = self.config.page_size
        pc = self.config.prefill_chunk
        if pc and self._prefill_chunk_fn is not None:
            while self._free_slots:
                # Peek for a long-prompt request; admit it incrementally.
                if self._backlog and len(self._backlog[0].prompt) > pc:
                    req = self._backlog.pop(0)
                elif not self._backlog:
                    try:
                        req = self._waiting.get_nowait()
                    except queue.Empty:
                        break
                    if len(req.prompt) <= pc:
                        # Short prompt — normal batched admission path.
                        self._backlog.insert(0, req)
                        break
                else:
                    break
                slot = self._alloc_slot_pages(req)
                if slot is None:
                    self._backlog.insert(0, req)
                    break
                req.admitted_at = time.monotonic()
                self._ring.record(
                    req.request_id, _reqev.PREFILLING, slot=slot,
                    num_pages=len(self._slot_pages.get(slot, [])))
                self._prefilling.append({"req": req, "slot": slot,
                                         "pos": 0})
        while self._free_slots:
            batch: List[Tuple[Request, int]] = []
            group_bucket = None
            while self._free_slots and len(batch) < 8:
                if self._backlog:
                    req = self._backlog.pop(0)
                else:
                    try:
                        req = self._waiting.get_nowait()
                    except queue.Empty:
                        break
                bucket = self._paged_bucket_for(len(req.prompt))
                if group_bucket is None:
                    group_bucket = bucket
                elif bucket != group_bucket:
                    # One bucket per compiled prefill group; mismatches
                    # lead the next group.
                    self._backlog.append(req)
                    break
                need = self._pages_needed(req)
                if len(self._free_pages) < need:
                    self._backlog.append(req)  # wait for page frees
                    break
                slot = self._alloc_slot_pages(req, need=need)
                if slot is None:
                    self._backlog.append(req)
                    break
                batch.append((req, slot))
            if not batch:
                return
            bucket = group_bucket
            k = 1
            while k < len(batch):
                k *= 2
            tokens = np.zeros((k, bucket), np.int32)
            true_lens = np.zeros((k,), np.int32)
            pages_rows = np.zeros((k, bucket // page), np.int32)
            temps = np.zeros((k,), np.float32)
            for i in range(k):
                req, slot = batch[min(i, len(batch) - 1)]  # pad = copy
                tokens[i, : len(req.prompt)] = req.prompt
                true_lens[i] = len(req.prompt)
                pages_rows[i] = self._bt[slot][: bucket // page]
                temps[i] = req.temperature
            slot_ids = np.asarray(
                [batch[min(i, len(batch) - 1)][1] for i in range(k)],
                np.int32)
            for req, slot in batch:
                self._lens[slot] = len(req.prompt)
            self._admitting = [req for req, _slot in batch]
            toks_dev = self._run_prefill(k, tokens, true_lens, pages_rows,
                                         temps,
                                         self._scatter_ids(slot_ids,
                                                           len(batch)))
            self._finish_admit(batch, toks_dev, slot_ids)

    def _admit_ragged(self):
        """Ragged admission: EVERY request (short or long) claims its
        slot + pages up front and joins the incremental-prefill track;
        the unified step packs its prompt in budget-sized chunks
        beside live decode rows, so there is no separate one-shot
        prefill program to head-of-line-block behind."""
        from ray_tpu.serve.adapter_pool import AdapterPoolPressure

        while self._free_slots:
            if self._backlog:
                req = self._backlog.pop(0)
            else:
                try:
                    req = self._waiting.get_nowait()
                except queue.Empty:
                    return
            if req.adapter_id and self._adapters is not None:
                # Borrow the adapter's pages for the slot's lifetime.
                # Pressure (nothing evictable: every resident adapter
                # is borrowed) is transient — back off like page
                # pressure.  A loader error is terminal for the
                # request, never the engine.
                try:
                    self._adapters.acquire(req.adapter_id)
                except AdapterPoolPressure:
                    self._backlog.insert(0, req)
                    return
                except Exception as e:
                    req.finished_at = time.monotonic()
                    self._observe_request(
                        req, state=_reqev.FAILED,
                        cause=f"adapter load failed: {e!r}")
                    req.stream.put(RuntimeError(
                        f"adapter {req.adapter_id!r} load failed: {e!r}"))
                    continue
            got = self._admit_slot_for(req)
            if got is None:
                if req.adapter_id and self._adapters is not None:
                    self._adapters.release(req.adapter_id)
                self._backlog.insert(0, req)
                return
            slot, start = got
            if req.adapter_id:
                self._slot_adapter[slot] = req.adapter_id
            req.admitted_at = time.monotonic()
            self._ring.record(
                req.request_id, _reqev.PREFILLING, slot=slot,
                num_pages=len(self._slot_pages.get(slot, [])),
                prefix_hit=req.prefix_hit)
            self._prefilling.append({"req": req, "slot": slot,
                                     "pos": start})
            self._state_dirty = True  # bt rows changed

    def _draft_alloc(self, req: Request, slot: int) -> bool:
        """Lazily claim draft-pool pages for a slot's first
        speculative round (sized like the target allocation — the
        draft sequence tracks the target's).  False = draft pool
        exhausted; the slot simply plain-decodes until pages free."""
        if slot in self._draft_slot_pages:
            return True
        need = self._pages_needed(req)
        if len(self._draft_free) < need:
            return False
        pages = [self._draft_free.pop() for _ in range(need)]
        self._draft_slot_pages[slot] = pages
        row = np.full((self._maxp,), self._draft_pages, np.int32)
        row[: len(pages)] = pages
        self._draft_bt[slot] = row
        self._draft_fed[slot] = 0
        return True

    def _run_draft_feed(self, feed_rows: List[Dict[str, Any]],
                        feed_tokens: int):
        """Dispatch one draft catch-up/draft-1 feed over the ragged
        packer; returns the device [R] per-row argmax (row i fed
        through its sequence end = that slot's first draft token)."""
        from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch

        R, Td = self.config.max_slots, self._token_budget
        (host_toks, _mask, _tok_slot, tok_pos, row_slot, row_start,
         row_len, row_off) = pack_ragged_batch(feed_rows, Td, R)
        self._draft_cache, nxt = self._instrumented_dispatch(
            "serve.spec_draft", self._draft_feed_fn,
            (self._draft_params, self._draft_cache, host_toks, tok_pos,
             row_slot, row_start, row_len, row_off,
             np.array(self._draft_bt)),
            span_name="llm.spec_draft")
        self._tm["step_tokens"].inc(feed_tokens,
                                    tags={"phase": "spec_draft"})
        return nxt

    def _spec_rem(self, req: Request) -> int:
        """Tokens the request may still emit (no in-flight charge —
        speculation only plans on fully-idle slots)."""
        return min(
            req.max_new_tokens - len(req.tokens),
            self.config.max_seq_len - len(req.prompt) - len(req.tokens),
        )

    def _spec_draft_round(self) -> Dict[int, List[int]]:
        """Plan and run ONE draft round: pick this dispatch's
        speculation candidates, catch the draft KV up to each
        candidate's sequence (one ragged feed whose row logits are the
        first drafts), chain up to spec_k - 1 single-token draft
        steps, and return {slot: draft tokens} for every candidate
        whose drafts are ready to verify.  The stacked draft samples
        come back through ONE device_get — the inherent sync point of
        drafting; the verify step itself stays pipelined."""
        R, Td = self.config.max_slots, self._token_budget
        k_cfg = self.config.spec_k
        active = sorted(self._slot_req)
        # Every active slot takes at least one token of the verify
        # dispatch's budget; a candidate spends k_eff on top of it.
        budget_left = Td - len(active)
        feed_left = Td
        # (slot, req, k_eff, seq_len) — candidate i is feed row i.
        plan: List[Tuple[int, Request, int, int]] = []
        feed_rows: List[Dict[str, Any]] = []
        catchup_rows: List[Dict[str, Any]] = []
        feed_tokens = 0
        for slot in active:
            req = self._slot_req[slot]
            if (slot in self._spec_inflight
                    or self._inflight_tokens.get(slot, 0)
                    or req.temperature != 0.0 or req.adapter_id
                    or req.first_token_at is None):
                continue
            k_eff = min(k_cfg, self._spec_rem(req) - 1, budget_left)
            if k_eff < 1 or not self._draft_alloc(req, slot):
                continue
            seq = req.prompt + req.tokens
            fed = self._draft_fed.get(slot, 0)
            backlog = seq[fed:]
            if (len(backlog) > feed_left
                    or len(feed_rows) + len(catchup_rows) >= R):
                # Can't catch up this round: feed what fits (the KV
                # sticks across rounds) and plain-decode meanwhile.
                # Catch-up rows pack AFTER every candidate row so
                # candidate i stays feed row i.
                if feed_left > 0 and len(feed_rows) + len(
                        catchup_rows) < R:
                    part = backlog[:feed_left]
                    catchup_rows.append(
                        {"slot": slot, "start": fed,
                         "tokens": [int(t) for t in part]})
                    self._draft_fed[slot] = fed + len(part)
                    feed_tokens += len(part)
                    feed_left = 0
                continue
            feed_rows.append({"slot": slot, "start": fed,
                              "tokens": [int(t) for t in backlog]})
            feed_left -= len(backlog)
            feed_tokens += len(backlog)
            self._draft_fed[slot] = len(seq)
            plan.append((slot, req, k_eff, len(seq)))
            budget_left -= k_eff
        feed_rows += catchup_rows
        if not feed_rows:
            return {}
        nxt = self._run_draft_feed(feed_rows, feed_tokens)
        if not plan:
            return {}
        max_k = max(k for _s, _r, k, _n in plan)
        outs = [nxt]
        chain_tokens = 0
        row_off = np.arange(R, dtype=np.int32)
        for m in range(2, max_k + 1):
            row_slot = np.zeros((R,), np.int32)
            row_start = np.zeros((R,), np.int32)
            row_len = np.zeros((R,), np.int32)
            tok_pos = np.zeros((Td,), np.int32)
            for i, (slot, _req, k_eff, seq_len) in enumerate(plan):
                if k_eff < m:
                    continue  # shorter chains idle as len-0 rows
                row_slot[i] = slot
                row_start[i] = tok_pos[i] = seq_len + m - 2
                row_len[i] = 1
                chain_tokens += 1
            self._draft_cache, nxt = self._instrumented_dispatch(
                "serve.spec_chain", self._draft_chain_fn,
                (self._draft_params, self._draft_cache, outs[-1],
                 tok_pos, row_slot, row_start, row_len, row_off,
                 np.array(self._draft_bt)),
                span_name="llm.spec_draft")
            outs.append(nxt)
        if chain_tokens:
            self._tm["step_tokens"].inc(chain_tokens,
                                        tags={"phase": "spec_draft"})
        stacked = np.asarray(jax.device_get(jnp.stack(outs)))
        return {slot: [int(stacked[m, i]) for m in range(k_eff)]
                for i, (slot, _req, k_eff, _n) in enumerate(plan)}

    def _dispatch_ragged_step(self) -> bool:
        """One unified ragged step through its three phases on the
        loop's clock, tied together by ``seq`` (the step's ordinal,
        ``stats()["steps"]`` once it is dispatched): ``llm.pack`` builds
        the step's host arrays and records what it holds, ``llm.dispatch``
        is the jitted call, ``llm.commit`` advances the mirrors and hands
        the step to the fetch thread.  Returns False when nothing fit."""
        seq = self._steps + 1
        with self._clock.phase("pack") as span:
            step = self._pack_ragged_step()
            if step is None:
                return False
            name, fn, args, parts, finishing, counts = step
            span.set(seq=seq, **counts)
        with self._clock.phase("dispatch", {"seq": seq}):
            self._cache, toks_dev, self._cur_dev = \
                self._instrumented_dispatch(
                    name, fn, args,
                    span_name="llm.ragged", steps_attr="tokens",
                    cost_steps=float(counts["shape"]),
                    shape=counts["shape"],
                )
        with self._clock.phase("commit", {"seq": seq}):
            self._commit_ragged_step(parts, finishing, counts, toks_dev)
        return True

    def _pack_ragged_step(self):
        """Pack ONE unified ragged step: first a decode
        row (one token) or a speculative verify row (the slot's true
        last token + its k drafts) for every active slot with budget
        left, then prefill chunks from the incremental track until
        token_budget is full.  Decode rows are never displaced by
        prompt tokens — that priority IS the no-stall guarantee
        chunked prefill only approximates — and drafting never runs
        while prefill chunks contend for the budget.  Returns the
        program, its arguments and what the step holds, or None when
        nothing fit (every slot budget-capped by in-flight tokens, no
        prompt tokens pending)."""
        from ray_tpu.ops.ragged_paged_attention import (
            append_cell_count,
            pack_ragged_batch,
        )

        R = self.config.max_slots
        budget = self._token_budget
        rows: List[Dict[str, Any]] = []
        parts: List[Tuple[str, Request, int, int]] = []
        scatter = np.full((R,), R, np.int32)  # OOB = sample dropped
        temps = np.zeros((R,), np.float32)
        n_decode = n_prefill = n_spec = 0
        # Draft a speculative round only on uncontended dispatches:
        # pending prefill chunks always win the budget over draft
        # tokens, and a cold acceptance EMA pauses drafting outright.
        drafts: Dict[int, List[int]] = {}
        spec_round = (self._spec_on and bool(self._slot_req)
                      and not self._prefilling)
        if spec_round and self._spec_cooldown > 0:
            self._spec_cooldown -= 1
            spec_round = False
        if spec_round:
            drafts = self._spec_draft_round()
        # Per-step adapter gather set: distinct adapter ids -> index
        # 1..K-1 (0 is the null adapter).  A row whose adapter would
        # overflow the set simply waits for the next step.
        step_adapters: Dict[str, int] = {}

        def _adapter_idx(req: Request) -> Optional[int]:
            if not req.adapter_id or self._adapters is None:
                return 0
            idx = step_adapters.get(req.adapter_id)
            if idx is None:
                if (len(step_adapters)
                        >= self.config.max_batch_adapters - 1):
                    return None  # gather set full this step
                idx = len(step_adapters) + 1
                step_adapters[req.adapter_id] = idx
            return idx

        for slot in sorted(self._slot_req):
            if budget <= 0 or len(rows) >= R:
                break
            if self._spec_on and slot in self._spec_inflight:
                continue  # verify round in flight: slot fully idle
            req = self._slot_req[slot]
            rem = min(
                req.max_new_tokens - len(req.tokens),
                self.config.max_seq_len - len(req.prompt)
                - len(req.tokens),
            ) - self._inflight_tokens.get(slot, 0)
            if rem <= 0:
                continue  # budget fully covered by in-flight steps
            ai = _adapter_idx(req)
            if ai is None:
                continue
            seq_last = int(req.tokens[-1] if req.tokens
                           else req.prompt[-1])
            dr = drafts.get(slot)
            if dr and budget >= len(dr) + 1 and rem > len(dr):
                # Verify row: the slot's true last token plus its k
                # drafts, packed as ONE k+1-token prefill-chunk row at
                # the current KV length.  Target logits at every
                # candidate position come back in the verify vector;
                # the row's own sample keeps the OOB scatter (the
                # accept boundary is resolved host-side at fetch).
                i = len(rows)
                rows.append({"slot": slot,
                             "start": int(self._lens[slot]),
                             "tokens": [seq_last] + dr, "adapter": ai})
                parts.append(("verify", req, slot,
                              {"drafts": dr, "row": i,
                               "base_len": int(self._lens[slot])}))
                budget -= len(dr) + 1
                n_spec += len(dr) + 1
                continue
            if (spec_round and dr is None
                    and self._inflight_tokens.get(slot, 0) > 0
                    and req.temperature == 0.0 and not req.adapter_id
                    and req.first_token_at is not None
                    and self._spec_rem(req) >= 2
                    and (slot in self._draft_slot_pages
                         or len(self._draft_free)
                         >= self._pages_needed(req))):
                # Spec-eligible slot with steps still in flight: hold
                # further decode rows so its pipeline drains and the
                # NEXT round can draft for it — k accepted tokens per
                # verify step beats depth-k pipelining of one-token
                # steps.  Cooldown (cold acceptance) and prefill
                # contention clear spec_round, restoring full-depth
                # plain pipelining.
                continue
            i = len(rows)
            if self._spec_on and slot in self._spec_stale_cur:
                # The device cur went stale at the last verify round
                # (the accept boundary was resolved host-side): a
                # host-token row computes the identical decode step
                # and its scatter re-seeds cur.
                rows.append({"slot": slot,
                             "start": int(self._lens[slot]),
                             "tokens": [seq_last], "adapter": ai})
            else:
                rows.append({"slot": slot,
                             "start": int(self._lens[slot]),
                             "tokens": None, "adapter": ai})
            parts.append(("decode", req, slot, i))
            scatter[i] = slot
            temps[i] = req.temperature
            budget -= 1
            n_decode += 1
        finishing = []
        for st in self._prefilling:
            if budget <= 0 or len(rows) >= R:
                break
            req, slot, pos = st["req"], st["slot"], st["pos"]
            chunk = req.prompt[pos:pos + budget]
            if not chunk:
                continue
            ai = _adapter_idx(req)
            if ai is None:
                continue
            is_last = pos + len(chunk) >= len(req.prompt)
            i = len(rows)
            rows.append({"slot": slot, "start": pos,
                         "tokens": [int(t) for t in chunk],
                         "adapter": ai})
            temps[i] = req.temperature
            if is_last:
                # The final chunk's sample is the request's first
                # token; mid-chunk rows keep the OOB scatter id.
                parts.append(("first", req, slot, i))
                scatter[i] = slot
                finishing.append(st)
            st["pos"] = pos + len(chunk)
            budget -= len(chunk)
            n_prefill += len(chunk)
        if not rows:
            return None
        # The smallest compiled shape that holds the step's tokens; a
        # step with verify rows has its own program, at the budget.
        T = self._token_budget if n_spec else next(
            t for t in self._ragged_shapes if n_decode + n_prefill <= t)
        self._refresh_state_args()
        if step_adapters:
            # The step carries adapters: the program also gets the
            # pool, the step's page gather plan, and the per-token
            # adapter index.  Batches with no adapter rows never reach
            # here — they stay on the base trace (zero overhead,
            # bit-equal).
            (host_toks, decode_mask, tok_slot, tok_pos, row_slot,
             row_start, row_len, row_off, tok_adapter) = \
                pack_ragged_batch(rows, T, R, with_adapters=True)
            lora = (self._adapters.device_pool,
                    self._adapters.page_table(list(step_adapters)),
                    tok_adapter)
        else:
            (host_toks, decode_mask, tok_slot, tok_pos, row_slot,
             row_start, row_len, row_off) = pack_ragged_batch(rows, T, R)
            lora = None
        if n_spec:
            # Flat-buffer positions of every verify row's k+1
            # candidate tokens (static [Tv], padded with index 0 —
            # harmless extra gathers) + each part's offset into the
            # returned verify vector.
            logit_idx = np.zeros((self._spec_tv,), np.int32)
            row_off_np = np.asarray(row_off)
            voff = 0
            for kind, _req, _slot, info in parts:
                if kind != "verify":
                    continue
                n = len(info["drafts"]) + 1
                off = int(row_off_np[info["row"]])
                logit_idx[voff:voff + n] = np.arange(off, off + n)
                info["voff"] = voff
                voff += n
        args = (self._params, self._cache, host_toks, decode_mask,
                tok_slot, tok_pos, row_slot, row_start, row_len,
                row_off, temps, self._next_seed(), self._cur_dev,
                scatter, self._bt_arg, lora)
        if n_spec:
            name, fn = "serve.ragged_spec", self._ragged_step_spec_fn
            args += (logit_idx,)
        else:
            name, fn = "serve.ragged", self._ragged_step_fn
        page = self.config.page_size
        counts = {
            "n_decode": n_decode, "n_prefill": n_prefill,
            "n_spec": n_spec, "rows": len(rows),
            "budget": self._token_budget,
            # the positions this step's program was compiled for
            "shape": T,
            # pages that hold each packed row's tokens once this step
            # has written them, against the cells the step's attention
            # kernel walks, as the adapter states them
            "live_cells": sum(
                -(-(r["start"] + len(r["tokens"] or (0,))) // page)
                for r in rows),
            "grid_cells": (
                self._ragged_grid_cells(row_start, row_len, self._maxp,
                                        page, bool(step_adapters))
                if self._ragged_grid_cells
                else R * (self._maxp + 1)),
            # pages this step's fresh tokens land in: the cells the
            # append kernel walks in each layer
            "append_cells": append_cell_count(row_start, row_len, page),
            # rows that start a sequence (a recurrent-state cache resets
            # their slot on the device) and the step's longest row (what
            # a scan over a row's tokens walks)
            # tokens the packed rows' sequences already hold: what a
            # step's attention reads of the pool, to the token
            "ctx_tokens": sum(r["start"] for r in rows),
            "n_state_reset": sum(1 for r in rows if r["start"] == 0),
            "scan_len": max(len(r["tokens"] or (0,)) for r in rows),
        }
        if self._ragged_sel_tokens:
            counts["sel_tokens"] = self._ragged_sel_tokens(row_start,
                                                           row_len)
        if not self._paged_kv:      # no page, so no cell of any
            counts.update(live_cells=0, grid_cells=0, append_cells=0,
                          ctx_tokens=0)
        return name, fn, args, parts, finishing, counts

    def _commit_ragged_step(self, parts, finishing, counts,
                            toks_dev) -> None:
        """The dispatched step's bookkeeping: host mirrors advance at
        dispatch, counters count, the fetch thread gets the result."""
        n_decode, n_prefill, n_spec = (
            counts["n_decode"], counts["n_prefill"], counts["n_spec"])
        now = time.monotonic()
        for kind, req, slot, i in parts:
            if kind == "verify":
                # The slot idles until its accept boundary returns:
                # lens only advances at fetch — that deferral IS the
                # rejection rollback point.
                self._inflight_tokens[slot] = len(i["drafts"]) + 1
                self._spec_inflight.add(slot)
                continue
            if kind == "decode":
                self._lens[slot] += 1  # mirror advances at dispatch
                if self._spec_on:
                    # A host-token decode row's scatter re-seeded cur.
                    self._spec_stale_cur.discard(slot)
            self._inflight_tokens[slot] = \
                self._inflight_tokens.get(slot, 0) + 1
        for st in finishing:
            self._prefilling.remove(st)
            req, slot = st["req"], st["slot"]
            self._lens[slot] = len(req.prompt)
            self._slot_req[slot] = req
            self._temps[slot] = req.temperature
            if req.admitted_at is None:
                req.admitted_at = now
        self._state_dirty = True
        self._steps += 1
        self._steps_by_shape[counts["shape"]] += 1
        self._tm["steps"].inc(tags={"shape": str(counts["shape"])})
        self._tm["step_tokens"].inc(n_decode, tags={"phase": "decode"})
        self._tm["step_tokens"].inc(n_prefill,
                                    tags={"phase": "prefill"})
        if n_spec:
            self._tm["step_tokens"].inc(n_spec,
                                        tags={"phase": "spec_verify"})
        if self._state_bytes_per_slot and counts["n_state_reset"]:
            self._state_resets += counts["n_state_reset"]
            self._tm["state_resets"].inc(counts["n_state_reset"])
        self._count_collective_bytes(n_decode)
        if n_decode:
            self._tm["batch_size"].observe(n_decode)
        self._tm["queue_depth"].set(self._waiting.qsize()
                                    + len(self._backlog))
        self._tm["queue_age"].set(self._admission_queue_age())
        self._unprocessed += 1
        self._clock.step_dispatched()
        self._fetchq.put(("ragged", toks_dev, 1, list(parts), self._steps))

    def _emit(self, req: Request, slot: int, tok: int, burst: int = 1):
        """Record one generated token; finish/free the slot if done.
        ``burst`` > 1 = one of several tokens emitted by a single
        speculative verify step: the round's wall gap is split evenly
        across the burst so the ITL histogram stays an exact per-token
        partition of decode wall time."""
        self._slot_req.setdefault(slot, req)
        now = time.monotonic()
        if req.last_token_at is not None:
            gap = (now - req.last_token_at) / max(burst, 1)
            req.max_itl_s = max(req.max_itl_s, gap)
        req.last_token_at = now
        req.tokens.append(tok)
        req.token_seqs.append(self._emit_seq)
        req.stream.put(tok)
        self._tokens_out += 1
        self._ring.update(req.request_id,
                          generated_tokens=len(req.tokens))
        eos = self.config.eos_id is not None and tok == self.config.eos_id
        done = (
            eos
            or len(req.tokens) >= req.max_new_tokens
            or len(req.prompt) + len(req.tokens) >= self.config.max_seq_len
        )
        if done:
            cause = ("eos" if eos
                     else "max_new_tokens"
                     if len(req.tokens) >= req.max_new_tokens
                     else "max_seq_len")
            # KV is written for prompt + generated minus the last
            # sampled token (it was never fed back) — exactly the
            # prefix a future request can resume from.
            seq = req.prompt + req.tokens
            self._release_slot(slot, cache_tokens=seq[:len(seq) - 1])
            req.finished_at = now
            self._observe_request(req, state=_reqev.FINISHED, cause=cause)
            req.stream.put(_DONE)

    def _finish_verify(self, req: Request, slot: int,
                       info: Dict[str, Any], ver: np.ndarray,
                       now: float) -> None:
        """Resolve one fetched verify round: accept the longest draft
        prefix that matches the target argmaxes plus the free bonus
        token the target computed past it, rewind the slot's KV write
        offset (the host length mirror) to the accept boundary, and
        emit the burst.

        Rollback safety: the target wrote KV for all k+1 candidate
        positions in-place, but ``_lens[slot]`` only ever advances to
        ``base_len + 1 + j`` — every later step (and the draft feed)
        writes from the mirror, so rejected tail positions are
        overwritten before anything can attend to them, the grow-only
        int8 per-page scales merely stay conservative for the
        overwritten tail, and the finish path donates only
        ``seq[:-1]`` pages (always inside the accepted prefix) to the
        prefix trie — rejected positions never become cache-visible."""
        self._spec_inflight.discard(slot)
        # The whole k+1 charge pops at once: speculation only launches
        # on slots with zero in-flight tokens, so the charge is
        # exactly this round's.
        self._inflight_tokens.pop(slot, None)
        drafts, base_len = info["drafts"], info["base_len"]
        k = len(drafts)
        voff = info["voff"]
        row_ver = [int(t) for t in ver[voff:voff + k + 1]]
        j = 0
        while j < k and drafts[j] == row_ver[j]:
            j += 1
        self._spec_rounds += 1
        self._spec_drafted_total += k
        self._spec_accepted_total += j
        self._tm["spec_rounds"].inc()
        self._tm["spec_drafted"].inc(k)
        if j:
            self._tm["spec_accepted"].inc(j)
        self._tm["spec_accept_ratio"].set(
            self._spec_accepted_total / self._spec_drafted_total)
        self._spec_ema = 0.8 * self._spec_ema + 0.2 * (j / k)
        if (self._spec_cooldown == 0
                and self._spec_ema < self.config.spec_cold_accept):
            # Acceptance ran cold: plain-decode for a while, then
            # re-probe with a reset EMA.
            self._spec_cooldown = self.config.spec_cooldown_rounds
            self._spec_cooldowns += 1
            self._spec_ema = 1.0
        # Draft-KV rollback: the draft fed tokens seq[-1], d1..d(k-1)
        # at positions base_len+1 .. base_len+k, of which the first
        # min(j, k-1) drafts survive — d(k) was never fed back.
        self._draft_fed[slot] = base_len + 1 + min(j, k - 1)
        if req.finished_at is not None or self._slot_req.get(slot) is not req:
            return  # cancelled/preempted while the verify was in flight
        # Target-KV rollback happens HERE, before any emit can finish
        # the request and donate pages: the write offset rewinds to
        # the accept boundary.
        self._lens[slot] = base_len + 1 + j
        self._state_dirty = True
        # Device cur holds the verify row's (dropped) sample, not the
        # accept boundary — the next decode row for this slot feeds
        # the true last token from the host and re-seeds cur.
        self._spec_stale_cur.add(slot)
        req.spec_drafted += k
        req.spec_accepted += j
        self._ring.update(req.request_id,
                          spec_drafted=req.spec_drafted,
                          spec_accepted=req.spec_accepted)
        emitted = drafts[:j] + [row_ver[j]]
        for tok in emitted:
            self._emit(req, slot, int(tok), burst=len(emitted))
            if req.finished_at is not None:
                break  # EOS/limits inside the burst: drop the tail

    def _release_slot(self, slot: int, *,
                      cache_tokens: Optional[List[int]] = None) -> None:
        """Return a slot and its pages to the free pool —
        shared by the finish, cancel, and failure paths so terminal
        accounting can never leak capacity.

        With the prefix cache on: borrowed pages go back to the index
        (refcount -1, never the free list), and — on the FINISH path
        only (``cache_tokens`` = the KV-written token sequence) — the
        slot's full pages are offered to the trie; pages the trie
        adopts stay cached, the rest are freed.  Cancel/preempt/crash
        paths pass no cache_tokens: their tail pages may be partially
        written, so nothing is donated."""
        self._slot_req.pop(slot, None)
        aid = self._slot_adapter.pop(slot, "")
        if aid and self._adapters is not None:
            self._adapters.release(aid)
        self._free_slots.append(slot)
        self._state_dirty = True
        self._auditor.mark_dirty()
        if self._spec_on:
            self._spec_inflight.discard(slot)
            self._spec_stale_cur.discard(slot)
            self._draft_fed.pop(slot, None)
            dpages = self._draft_slot_pages.pop(slot, None)
            if dpages:
                if _audit.corrupt(_audit.INJECT_DRAFT_PAGE):
                    dpages = dpages[1:]  # leak one draft page
                self._draft_free.extend(dpages)
                self._draft_bt[slot] = self._draft_pages
        pages = self._slot_pages.pop(slot, [])
        if self._prefix is not None:
            borrowed = self._slot_borrowed.pop(slot, [])
            release = borrowed
            if borrowed and _audit.corrupt(_audit.INJECT_TRIE_REF):
                release = borrowed[1:]  # leak one trie borrow ref
            self._prefix.release(release)
            adopted: set = set()
            if cache_tokens is not None and not self._draining.is_set():
                full = len(cache_tokens) // self.config.page_size
                adopted = self._prefix.insert(cache_tokens,
                                              pages[:full])
            owned = pages[len(borrowed):]
            self._free_pages.extend(p for p in owned
                                    if p not in adopted)
        else:
            self._free_pages.extend(pages)
        self._bt[slot] = self._num_pages
        self._lens[slot] = 0
        self._update_page_gauges()

    def _slo_met(self, req: Request) -> bool:
        """Did a FINISHED request meet every configured bound?  (No slo
        config = trivially met; callers gate on the terminal state.)"""
        slo = self.config.slo
        if slo is None:
            return True
        if slo.ttft_s is not None and (
                req.ttft_s is None or req.ttft_s > slo.ttft_s):
            return False
        if slo.tpot_s is not None:
            if req.first_token_at is None or len(req.tokens) < 2:
                return False
            tpot = ((req.finished_at - req.first_token_at)
                    / (len(req.tokens) - 1))
            if tpot > slo.tpot_s:
                return False
        if slo.e2e_s is not None and (
                req.finished_at - req.submitted_at) > slo.e2e_s:
            return False
        return True

    def _observe_request(self, req: Request, *,
                         state: str = _reqev.FINISHED,
                         cause: Optional[str] = None) -> None:
        """Terminal-state accounting for EVERY outcome — ring verdict,
        SLO/goodput/terminal counters for all three terminal states,
        latency histograms only for FINISHED (a cancelled request has
        no honest TTFT), and the request's span tree (queue wait →
        prefill → decode) when tracing is on.  Spans are recorded
        retroactively from the monotonic stamps the engine loop takes
        anyway, so the decode hot path itself carries no tracing
        code."""
        self._ring.record(req.request_id, state,
                          generated_tokens=len(req.tokens),
                          terminal_cause=cause,
                          spec_drafted=req.spec_drafted or None,
                          spec_accepted=(req.spec_accepted
                                         if req.spec_drafted else None))
        finished = state == _reqev.FINISHED
        met = finished and self._slo_met(req)
        if finished and not met and self.config.slo is not None:
            try:
                from ray_tpu.util import flight_recorder
                flight_recorder.trigger("slo_miss",
                                        request_id=req.request_id)
            except Exception:
                pass
        self._tm["terminal"].inc(tags={"state": state})
        self._tm["slo"].inc(tags={"outcome": "met" if met else "missed"})
        self._terminal_tokens += len(req.tokens)
        if met:
            self._good_tokens += len(req.tokens)
        if self._terminal_tokens:
            self._tm["goodput"].set(
                self._good_tokens / self._terminal_tokens)
        log.debug("request %s %s (cause=%s, %d tokens)",
                  req.request_id, state, cause, len(req.tokens))
        if finished:
            if req.ttft_s is not None:
                self._tm["ttft"].observe(req.ttft_s)
            if (req.first_token_at is not None and len(req.tokens) > 1):
                self._tm["tpot"].observe(
                    (req.finished_at - req.first_token_at)
                    / (len(req.tokens) - 1))
                self._tm["itl"].observe(req.max_itl_s)
        # Waterfall attribution: partition this request's e2e wall into
        # the raytpu_serve_request_overhead_seconds components and fold
        # it into the control-plane-share gauge (engine-local rows —
        # the router-inclusive join stays driver-side).
        try:
            from ray_tpu.serve import latency_attribution as _lat
            row = self._ring.row(req.request_id)
            if row is not None:
                _lat.observe_terminal(req.request_id, rows=[row])
        except Exception:
            pass  # attribution is best-effort accounting
        if not tracing.is_enabled():
            return
        # Monotonic stamps → wall clock for the trace view.
        off = time.time() - time.monotonic()
        root = tracing.record_span(
            "llm.request", req.submitted_at + off, req.finished_at + off,
            ctx=req.trace_ctx,
            attributes={"request_id": req.request_id,
                        "state": state,
                        "terminal_cause": cause,
                        "prompt_len": len(req.prompt),
                        "num_tokens": len(req.tokens)},
        )
        ctx = {"trace_id": root["trace_id"], "span_id": root["span_id"]}
        # A never-admitted terminal (cancelled/failed in queue) spends
        # its whole life in queue_wait.
        admitted = req.admitted_at or req.finished_at
        tracing.record_span("llm.queue_wait", req.submitted_at + off,
                            admitted + off, ctx=ctx)
        if req.first_token_at is not None:
            tracing.record_span("llm.prefill", admitted + off,
                                req.first_token_at + off, ctx=ctx)
            tracing.record_span("llm.decode", req.first_token_at + off,
                                req.finished_at + off, ctx=ctx,
                                attributes={"tokens": len(req.tokens)})

    def _chunk_size(self) -> int:
        """Largest compiled chunk that no active request can out-finish
        given tokens ALREADY IN FLIGHT (so only EOS, never the token
        budget, can end a request mid-chunk); 0 = every budget is fully
        covered by in-flight chunks — process those first.  The ladder
        is descending powers of two, so a gen-31 tail costs
        16+8+4+2+1 = 5 dispatches, not 16+4+4+4+1+1+1.

        Sizing keys off the LONGEST-remaining active request: shorter
        requests finish mid-chunk (their lanes decode garbage for the
        chunk's tail — batched decode computes every lane anyway, and
        overshoot writes are OOB-dropped via the block-table sentinel).
        min-sizing would fragment chunks whenever staggered arrivals
        mix progress levels — the open-loop serving pattern."""
        remaining = max(
            min(
                req.max_new_tokens - len(req.tokens),
                self.config.max_seq_len - len(req.prompt) - len(req.tokens),
            ) - self._inflight_tokens.get(slot, 0)
            for slot, req in self._slot_req.items()
        )
        for k in self._chunk_ladder:
            if k <= remaining:
                return k
        if remaining > 0:
            return self._chunk_ladder[-1]  # 1-step chunk covers any tail
        return 0

    def _dispatch_prefill_chunk(self) -> None:
        """Advance ONE incremental prefill by one chunk (interleaved
        with decode chunks, so a long prompt never blocks streams for
        its whole prefill — chunked prefill à la Sarathi/vLLM).  Each
        chunk enters the fetch pipe as a completion marker, so chunk
        dispatch is pipeline-gated like decode — the device queue never
        floods with back-to-back prefill chunks."""
        st = self._prefilling[0]
        req, slot, pos = st["req"], st["slot"], st["pos"]
        C = self.config.prefill_chunk
        chunk = req.prompt[pos:pos + C]
        t = np.zeros((1, C), np.int32)
        t[0, : len(chunk)] = chunk
        slot_arr = np.asarray([slot], np.int32)
        is_last = pos + len(chunk) >= len(req.prompt)
        scatter = (slot_arr if is_last
                   else np.asarray([self.config.max_slots], np.int32))
        # Attend only over pages covering the prompt so far (rounded to
        # a power of two for compile-shape bucketing) — a 256-token
        # chunk must not pay max_seq_len-wide attention.
        page = self.config.page_size
        covered = -(-(pos + len(chunk)) // page)
        nb = 1
        while nb < covered:
            nb *= 2
        nb = min(nb, self._maxp)
        self._cache, toks_dev, self._cur_dev = self._prefill_chunk_fn(
            self._params, self._cache, t,
            np.asarray([pos], np.int32),
            np.asarray([len(chunk)], np.int32),
            self._bt[slot][None, :nb],
            np.asarray([req.temperature], np.float32),
            self._next_seed(), self._cur_dev, scatter,
        )
        st["pos"] = pos + len(chunk)
        self._tm["step_tokens"].inc(len(chunk),
                                    tags={"phase": "prefill"})
        if is_last:
            self._prefilling.pop(0)
            self._lens[slot] = len(req.prompt)
            self._finish_admit([(req, slot)], toks_dev, slot_arr)
        else:
            # Completion marker: counts against the pipeline depth.
            self._unprocessed += 1
            self._clock.step_dispatched()
            self._fetchq.put(("pfchunk", toks_dev, 0, [], self._steps))

    def _refresh_state_args(self) -> None:
        """Rebuild the per-slot control arrays only when admission or a
        finish changed them; the arrays ride the next dispatch as jit
        arguments (no separate upload ops).  Between changes, lens
        feeds back device-side from the previous decode."""
        if not self._state_dirty:
            return
        active = np.zeros((self.config.max_slots,), bool)
        for slot in self._slot_req:
            active[slot] = True
        self._active_arg = active
        self._temps_arg = np.array(self._temps)
        self._bt_arg = np.array(self._bt)
        self._lens_arg = np.array(self._lens)
        self._state_dirty = False

    def _admission_queue_age(self) -> float:
        """Seconds since the oldest still-unadmitted request was
        submitted (0.0 when nothing waits).  Snapshot over the waiting
        queue's and backlog's internals — both only ever hold Request
        objects and a stale read just shifts the gauge one sample."""
        oldest = None
        for req in list(self._waiting.queue) + list(self._backlog):
            if oldest is None or req.submitted_at < oldest:
                oldest = req.submitted_at
        return 0.0 if oldest is None else time.monotonic() - oldest

    def _on_loop_stall(self, *, phase: str, wall_ms: float,
                       seq: Optional[int], median_ms: float) -> None:
        """The loop clock found an iteration, or a step interval, far
        past the running median: say which phase held it, leave one
        event in the flight recorder, and (rate-limited) arm a bundle,
        so that what every other thread recorded around the stall is
        kept."""
        log.warning(
            "engine loop stall: %.1f ms in phase %r near step %s "
            "(running median step interval %.1f ms, active=%d)",
            wall_ms, phase, seq, median_ms, len(self._slot_req))
        try:
            from ray_tpu.util import flight_recorder

            # ``step_seq``: the recorder numbers its own events ``seq``
            flight_recorder.record("loop_stall", phase=phase,
                                   wall_ms=wall_ms, step_seq=seq,
                                   engine=self._engine_id)
            now = time.monotonic()
            if now - self._stall_trigger_at >= LOOP_STALL_TRIGGER_INTERVAL_S:
                self._stall_trigger_at = now
                flight_recorder.trigger("loop_stall", detail=phase)
        except Exception:
            pass  # the recorder must never take the loop down with it

    def _dispatch_decode(self, chunk: int) -> None:
        """Enqueue one decode chunk WITHOUT a host sync: cur and lens
        come back as device outputs of the previous chunk, so this runs
        while earlier chunks' tokens are still on their way to the host
        (the dispatch pipeline).  Its depth and decode_chunk=16 were
        sized for a host<->device round trip of about 100 ms; whether a
        directly attached chip needs either is ROADMAP Queue 1
        items 2-3."""
        self._refresh_state_args()
        self._cache, toks_dev, self._cur_dev, self._lens_arg = \
            self._instrumented_dispatch(
                "serve.decode", self._decode_fn,
                (chunk, self._params, self._cache, self._cur_dev,
                 self._active_arg, self._temps_arg,
                 self._next_seed(), self._bt_arg, self._lens_arg),
                span_name="llm.decode", steps_attr="tokens",
                # One decode step produces one token per active
                # request: a request's per-token device share is a
                # full step, so the denominator is steps, not
                # steps x slots.
                cost_steps=float(chunk),
            )
        # Host mirror advances for slots active in THIS dispatch.
        for slot in self._slot_req:
            self._lens[slot] += chunk
        self._steps += chunk
        self._tm["step_tokens"].inc(chunk * len(self._slot_req),
                                    tags={"phase": "decode"})
        self._count_collective_bytes(len(self._slot_req), steps=chunk)
        self._tm["batch_size"].observe(len(self._slot_req))
        self._tm["queue_depth"].set(
            self._waiting.qsize() + len(self._backlog))
        self._tm["queue_age"].set(self._admission_queue_age())
        participants = list(self._slot_req.items())
        for slot, _req in participants:
            self._inflight_tokens[slot] = (
                self._inflight_tokens.get(slot, 0) + chunk
            )
        self._unprocessed += 1
        self._clock.step_dispatched()
        self._fetchq.put(("decode", toks_dev, chunk, participants,
                          self._steps))

    def _fetch_loop(self) -> None:
        """Dedicated fetch thread: hand each step's host arrays back to
        the engine loop in dispatch order, as soon as that step has run.
        One device_get takes the oldest queued entry and every later one
        whose arrays are ready already: a fetcher that fell behind
        catches up in one call, and one that keeps up lets a step's
        tokens leave when the step ends, not when the newest step
        queued behind it does (a burst per pipeline depth)."""
        pending: List[Any] = []
        while not self._stopped.is_set():
            if not pending:
                pending.append(self._fetchq.get())
            while True:
                try:
                    pending.append(self._fetchq.get_nowait())
                except queue.Empty:
                    break
            if any(e is None for e in pending):
                return
            ready = 1
            while ready < len(pending) and all(
                    a.is_ready()
                    for a in jax.tree_util.tree_leaves(pending[ready][1])):
                ready += 1
            entries, pending = pending[:ready], pending[ready:]
            try:
                # Entries leave in dispatch order, so their steps are
                # the range first..last (numbers only: a profiler stat
                # that is a list in text reads back as its last item).
                with tracing.span("llm.fetch", record=False, attributes={
                        "seq_first": entries[0][4],
                        "seq_last": entries[-1][4],
                        "seqs": len(entries)}):
                    fetched = jax.device_get([e[1] for e in entries])
            except BaseException as e:
                self._fetched.put(e)
                return
            for entry, toks in zip(entries, fetched):
                # Speculative ragged steps return (sampled, verify)
                # as a tuple payload — keep the structure.
                if isinstance(toks, tuple):
                    toks = tuple(np.asarray(t) for t in toks)
                else:
                    toks = np.asarray(toks)
                self._fetched.put((entry, toks))

    def _process_fetched(self, block: bool) -> bool:
        """Emit every fetched entry available; returns True if any was
        processed.  ``block`` waits briefly for the next one (used when
        the loop has steps in flight and nothing to dispatch: phase
        ``wait``, not ``idle``, which is the engine empty)."""
        try:
            if block:
                with self._clock.phase(
                        "wait", {"in_flight": self._unprocessed}):
                    item = self._fetched.get(timeout=0.02)
            else:
                item = self._fetched.get_nowait()
        except queue.Empty:
            return False
        with self._clock.phase("emit") as span:
            seqs: List[int] = []
            steps = 0
            while item is not None:
                if isinstance(item, BaseException):
                    raise item
                self._unprocessed -= 1
                seqs.append(item[0][4])
                steps += self._emit_fetched(item)
                try:
                    item = self._fetched.get_nowait()
                except queue.Empty:
                    item = None
            span.set(seq_first=seqs[0], seq_last=seqs[-1], seqs=len(seqs))
            # The steps that came back together share one interval of
            # the loop clock's pace.
            self._clock.steps_fetched(steps, self._unprocessed, seqs[-1])
        return True

    def _emit_fetched(self, item) -> int:
        """Emit one fetched entry's tokens; returns the decode steps it
        carried (0 for a prefill entry or a completion marker)."""
        (kind, _dev, chunk, participants, seq), toks = item
        self._emit_seq = seq    # ``_emit`` notes it beside each token
        now = time.monotonic()
        if kind == "pfchunk":
            return 0  # completion marker only (pipeline gating)
        if kind == "ragged":
            # One unified step: toks is the [R] row-sample vector;
            # participants carry (kind, req, slot, row) for decode
            # rows and final prefill chunks (mid-chunk rows have
            # nothing to emit).  A ragged step IS a decode step for
            # every running stream in it.
            if isinstance(toks, tuple):
                toks, ver = toks  # speculative step: (sampled, verify)
            else:
                ver = None
            for rkind, req, slot, i in participants:
                if rkind == "verify":
                    self._finish_verify(req, slot, i, ver, now)
                    continue
                left = self._inflight_tokens.get(slot, 0) - 1
                if left > 0:
                    self._inflight_tokens[slot] = left
                else:
                    self._inflight_tokens.pop(slot, None)
                if req.finished_at is not None:
                    continue  # cancelled/preempted while in flight
                if rkind == "first":
                    req.first_token_at = now
                    self._ring.record(req.request_id,
                                      _reqev.DECODING)
                    self._emit(req, slot, int(toks[i]))
                elif self._slot_req.get(slot) is req:
                    self._emit(req, slot, int(toks[i]))
            return 1
        if kind == "prefill":
            for i, (req, slot) in enumerate(participants):
                left = self._inflight_tokens.get(slot, 0) - 1
                if left > 0:
                    self._inflight_tokens[slot] = left
                else:
                    self._inflight_tokens.pop(slot, None)
                if req.finished_at is not None:
                    # Cancelled while its prefill was in flight:
                    # the slot is already freed (and may even be
                    # re-owned) — emitting would re-register it.
                    continue
                req.first_token_at = now
                self._ring.record(req.request_id, _reqev.DECODING)
                self._emit(req, slot, int(toks[i]))
            return 0
        for slot, req in participants:
            left = self._inflight_tokens.get(slot, 0) - chunk
            if left > 0:
                self._inflight_tokens[slot] = left
            else:
                self._inflight_tokens.pop(slot, None)
            if self._slot_req.get(slot) is not req:
                # Finished in an earlier chunk (EOS): overshoot.
                continue
            for k in range(chunk):
                self._emit(req, slot, int(toks[k, slot]))
                if self._slot_req.get(slot) is not req:
                    break  # finished mid-chunk
        return chunk

    def _process_cancels(self) -> None:
        """Resolve pending cancellations against every registry the
        loop owns.  A cancelled request releases its slot/pages and
        reaches CANCELLED through the same `_observe_request` path as
        every other terminal — its stream ends with the normal _DONE
        marker.  Unknown ids (already terminal, or never this
        engine's) are dropped silently: cancel is idempotent."""
        with self._cancel_lock:
            if not self._cancels:
                return
            pending = set(self._cancels)
            self._cancels.clear()

        def _finish_cancel(req: Request, slot: Optional[int]) -> None:
            if slot is not None:
                self._release_slot(slot)
            req.finished_at = time.monotonic()
            self._observe_request(req, state=_reqev.CANCELLED,
                                  cause="cancelled")
            req.stream.put(_DONE)

        for slot, req in list(self._slot_req.items()):
            if req.request_id in pending:
                pending.discard(req.request_id)
                _finish_cancel(req, slot)
        for st in list(self._prefilling):
            if st["req"].request_id in pending:
                pending.discard(st["req"].request_id)
                self._prefilling.remove(st)
                _finish_cancel(st["req"], st["slot"])
        for req in list(self._backlog):
            if req.request_id in pending:
                pending.discard(req.request_id)
                self._backlog.remove(req)
                _finish_cancel(req, None)
        if pending:
            kept: List[Request] = []
            while True:
                try:
                    req = self._waiting.get_nowait()
                except queue.Empty:
                    break
                if req.request_id in pending:
                    pending.discard(req.request_id)
                    _finish_cancel(req, None)
                else:
                    kept.append(req)
            for req in kept:
                self._waiting.put(req)

    def _preempt_request(self, req: Request,
                         slot: Optional[int]) -> None:
        """Evict one request with a PREEMPTED terminal.  Its stream
        ends by raising PreemptedError carrying the continuation
        payload, so the consumer knows exactly which generated prefix
        it already holds."""
        if slot is not None:
            self._release_slot(slot)
        req.finished_at = time.monotonic()
        self._observe_request(req, state=_reqev.PREEMPTED,
                              cause="preempted")
        self._preempted_count += 1
        req.stream.put(PreemptedError(
            "replica draining: request evicted",
            continuation={"prompt": list(req.prompt),
                          "tokens": list(req.tokens),
                          "temperature": req.temperature,
                          "request_id": req.request_id,
                          "adapter_id": req.adapter_id}))

    def _process_drain(self) -> None:
        """Loop-side half of drain(): while draining, requests that
        never reached a slot are preempted immediately (admission has
        stopped, they can only rot); once the grace window expires
        (_drain_evict), everything still in a slot goes too."""
        if not self._draining.is_set():
            return
        while True:
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                break
            self._preempt_request(req, None)
        for req in list(self._backlog):
            self._backlog.remove(req)
            self._preempt_request(req, None)
        if not self._drain_evict.is_set():
            return
        for st in list(self._prefilling):
            self._prefilling.remove(st)
            self._preempt_request(st["req"], st["slot"])
        for slot, req in list(self._slot_req.items()):
            self._preempt_request(req, slot)
        # Drain-evict leak fix (mirrors the clean-stop tail): open
        # migration leases belong to exports that can no longer
        # complete against a draining replica — release them, then
        # audit once so scale-down provably hands back a leak-free
        # pool.
        if not self._drain_audited:
            self._drain_audited = True
            self._release_open_leases()
            try:
                self._auditor.run(deep=True)
            except Exception:
                log.exception("drain-evict audit failed")

    # -- KV page migration (serve/kv_transfer) ------------------------------

    def _migration_op(self, kind: str, timeout_s: float, **kw) -> Any:
        """Enqueue one migration verb for the LOOP thread and wait for
        its result (the cache is donated between jitted dispatches, so
        only the loop may gather/scatter it — the same ownership rule
        the cancel queue follows).  Re-raises whatever the verb raised
        over there."""
        if self._state_bytes_per_slot:
            raise ValueError(
                "KV migration ships pages only; this engine's cache "
                "holds per-slot recurrent state "
                f"({self._state_bytes_per_slot} bytes a slot) that no "
                "page carries, so a migrated prefix could not be "
                "resumed")
        if self._prefix is None:
            raise RuntimeError(
                "KV migration requires "
                "EngineConfig.prefix_cache=True (transfers are keyed "
                "by the prefix trie's chained path hashes)")
        if self._stopped.is_set():
            raise RuntimeError("engine stopped")
        op: Dict[str, Any] = {"kind": kind, "done": threading.Event(),
                              "result": None, "error": None,
                              "abandoned": False, **kw}
        with self._mig_lock:
            self._mig_ops.append(op)
        self._work.set()
        if not op["done"].wait(timeout_s):
            with self._mig_lock:
                if not op["done"].is_set():
                    # Still queued: pull it so the loop never runs it.
                    # Already in flight: flag it abandoned — the loop
                    # auto-releases a lease nobody will ever own (a
                    # leaked lease pins its pages against eviction
                    # forever) and drops the unread result.
                    try:
                        self._mig_ops.remove(op)
                    except ValueError:
                        op["abandoned"] = True
                    raise TimeoutError(
                        f"migration op {kind!r} not serviced within "
                        f"{timeout_s}s")
            # done was set between the wait() expiry and taking the
            # lock: the op completed, its result is usable.
        if op["error"] is not None:
            raise op["error"]
        return op["result"]

    def migration_lease(self, tokens: Sequence[int], *,
                        timeout_s: float = 30.0) -> Optional[dict]:
        """Pin the longest cached full-page prefix of ``tokens`` under
        an eviction-proof migration lease.  Returns ``{"lease_id",
        "pages", "tokens"}`` (tokens truncated to the leased depth), or
        None when not even one full page is cached.  The caller owns
        the lease and MUST ``migration_release`` it on every path —
        success, failure, and cancel."""
        return self._migration_op("lease", timeout_s,
                                  tokens=[int(t) for t in tokens])

    def migration_export(self, lease_id: str, *, mode: str = "int8",
                         timeout_s: float = 30.0) -> dict:
        """Serialize a leased page run into one transfer dict (the
        kv_transfer.encode_pages wire format: payload + per-page int8
        scales + chained path hashes + analytic wire bytes)."""
        return self._migration_op("export", timeout_s,
                                  lease_id=lease_id, mode=mode)

    def migration_release(self, lease_id: str, *,
                          timeout_s: float = 30.0) -> bool:
        """Drop a migration lease.  Idempotent — unknown ids return
        False, because failure cleanup must never raise over a lease
        that already went away."""
        return self._migration_op("release", timeout_s,
                                  lease_id=lease_id)

    def migration_ingest(self, transfer: dict, *,
                         timeout_s: float = 30.0) -> int:
        """Ingest one transfer into the local pool + prefix trie:
        verify content identity (chained CRC32 over the tokens), skip
        depths already cached, scatter the payload into freshly
        allocated pages, and insert them into the trie.  Truncates to
        the free-page budget so the ingested prefix stays contiguous
        from the root.  Returns the number of pages ingested."""
        return self._migration_op("ingest", timeout_s, transfer=transfer)

    def export_hot_prefixes(self, *, max_pages: int = 256,
                            mode: str = "int8",
                            timeout_s: float = 60.0) -> List[dict]:
        """Prefix migration, source side: lease + export + release each
        hot cached path (recency order, deduped) — a cold or newly
        scaled replica ingests the returned transfers instead of
        recomputing its cache."""
        return self._migration_op("hot_prefixes", timeout_s,
                                  max_pages=max_pages, mode=mode)

    def _process_migrations(self) -> None:
        if self._prefix is None:
            return
        with self._mig_lock:
            if not self._mig_ops:
                return
            ops, self._mig_ops = self._mig_ops, []
        handlers = {"lease": self._mig_do_lease,
                    "export": self._mig_do_export,
                    "release": self._mig_do_release,
                    "ingest": self._mig_do_ingest,
                    "hot_prefixes": self._mig_do_hot_prefixes}
        for op in ops:
            try:
                op["result"] = handlers[op["kind"]](op)
            except Exception as e:  # re-raised at the waiter; loop lives
                op["error"] = e
            with self._mig_lock:
                # A waiter that timed out mid-service marked the op
                # abandoned: nobody will read the result, so a lease
                # acquired here would leak (eviction-pinned pages with
                # no owner to release them) — drop it on the spot.  The
                # lock orders this against the waiter's flag write: if
                # the waiter loses the race, it sees done set and uses
                # the result normally.
                if (op["abandoned"] and op["kind"] == "lease"
                        and op.get("result") is not None):
                    self._mig_do_release(
                        {"lease_id": op["result"]["lease_id"]})
                    op["result"] = None
                op["done"].set()

    @staticmethod
    def _mig_pad_ids(pages: Sequence[int], fill: int) -> np.ndarray:
        """Pad a page-id run to the next power of two (bounds the jit
        compile count) with ``fill`` — the OOB scratch page, a valid
        index whose contents nothing reads."""
        n = max(1, len(pages))
        padded = 1 << (n - 1).bit_length()
        return np.asarray(list(pages) + [fill] * (padded - len(pages)),
                          np.int32)

    def _mig_do_lease(self, op: dict) -> Optional[dict]:
        page = self.config.page_size
        pages = self._prefix.lease_acquire(op["tokens"])
        if not pages:
            return None
        lease_id = f"mig-{self._engine_id}-{next(self._mig_lease_ids)}"
        self._mig_leases[lease_id] = {
            "pages": list(pages),
            "tokens": op["tokens"][:len(pages) * page]}
        return {"lease_id": lease_id, "pages": list(pages),
                "tokens": list(self._mig_leases[lease_id]["tokens"])}

    def _mig_do_export(self, op: dict) -> dict:
        from ray_tpu.serve import kv_transfer as _kvt

        lease = self._mig_leases.get(op["lease_id"])
        if lease is None:
            raise KeyError(f"unknown migration lease {op['lease_id']!r}")
        t0 = time.monotonic()
        pages = lease["pages"]
        ids = self._mig_pad_ids(pages, self._num_pages)
        gathered = jax.device_get(self._mig_gather_fn(self._cache, ids))
        n = len(pages)
        gathered = {k: (v[:, :, :n] if k in ("k", "v") else v[:, :n])
                    for k, v in gathered.items()}
        transfer = _kvt.encode_pages(
            gathered, tokens=lease["tokens"],
            page_size=self.config.page_size, mode=op["mode"])
        self._mig_counts["pages_out"] += n
        self._mig_counts["bytes_out"] += transfer["wire_bytes"]
        self._tm["mig_pages"].inc(n, tags={"direction": "out"})
        self._tm["mig_bytes"].inc(transfer["wire_bytes"],
                                  tags={"direction": "out"})
        self._tm["mig_seconds"].observe(time.monotonic() - t0,
                                        tags={"op": "export"})
        return transfer

    def _mig_do_release(self, op: dict) -> bool:
        lease = self._mig_leases.pop(op["lease_id"], None)
        if lease is None:
            return False
        self._prefix.lease_release(lease["pages"])
        return True

    def _mig_do_ingest(self, op: dict) -> int:
        from ray_tpu.serve import kv_transfer as _kvt

        transfer = op["transfer"]
        page = self.config.page_size
        if int(transfer["page_size"]) != page:
            raise ValueError(
                f"transfer page_size {transfer['page_size']} != local "
                f"pool page_size {page}")
        _kvt.verify_transfer(transfer)
        t0 = time.monotonic()
        tokens = [int(t) for t in transfer["tokens"]]
        n_full = len(tokens) // page
        # Depths the trie already holds keep their local pages.  The
        # borrow stays held across the eviction AND the insert below:
        # evict() reclaims any refcount-0 page, so releasing the hit
        # pages first would let it free pages the insert is about to
        # re-adopt — the same page simultaneously on _free_pages and in
        # the trie, i.e. silent KV corruption.
        hit = self._prefix.acquire(tokens)
        try:
            have = len(hit)
            need = n_full - have
            if need <= 0:
                return 0
            if len(self._free_pages) < need:
                freed = self._prefix.evict(need - len(self._free_pages))
                self._free_pages.extend(freed)
                if freed:
                    self._tm["prefix_evicted"].inc(len(freed))
            # Truncate (never reorder): the ingested prefix must stay
            # contiguous from the root or the hashes stop meaning
            # "path".
            need = min(need, len(self._free_pages))
            if need <= 0:
                return 0
            dst = [self._free_pages.pop() for _ in range(need)]
            quantized = (isinstance(self._cache, dict)
                         and "k_scale" in self._cache)
            payload = _kvt.decode_payload(
                transfer, quantized, self._cache["k"].dtype,
                start_page=have, end_page=have + need)
            ids = self._mig_pad_ids(dst, self._num_pages)
            pad = len(ids) - need
            dev = {}
            for key in ("k", "v"):
                arr = payload[key]
                if pad:
                    arr = np.concatenate(
                        [arr, np.zeros((arr.shape[0], arr.shape[1], pad)
                                       + arr.shape[3:], arr.dtype)],
                        axis=2)
                dev[key] = arr
            if quantized:
                for key in ("k_scale", "v_scale"):
                    arr = payload[key]
                    if pad:
                        arr = np.concatenate(
                            [arr, np.zeros((arr.shape[0], pad)
                                           + arr.shape[2:], arr.dtype)],
                            axis=1)
                    dev[key] = arr
            self._cache = self._mig_scatter_fn(self._cache, ids, dev)
            adopted = self._prefix.insert(tokens[:(have + need) * page],
                                          hit + dst)
            for p in dst:
                if p not in adopted:  # lost a race with a local insert
                    self._free_pages.append(p)
            n_in = sum(1 for p in dst if p in adopted)
        finally:
            if hit:
                self._prefix.release(hit)
        wire = int(transfer.get("wire_bytes", 0))
        self._mig_counts["pages_in"] += n_in
        self._mig_counts["bytes_in"] += wire
        self._tm["mig_pages"].inc(n_in, tags={"direction": "in"})
        self._tm["mig_bytes"].inc(wire, tags={"direction": "in"})
        self._tm["mig_seconds"].observe(time.monotonic() - t0,
                                        tags={"op": "ingest"})
        self._update_page_gauges()
        return n_in

    # -- invariant audits (serve/audit, util/doctor) ------------------------

    def _process_audits(self) -> None:
        """Service queued doctor() and read_cache() ops (``_on_loop``) on
        the loop thread — the only thread allowed to walk slot/page
        state, or to touch the cache tree, while the engine runs."""
        with self._audit_lock:
            if not self._audit_ops:
                return
            ops, self._audit_ops = self._audit_ops, []
        for op in ops:
            try:
                op["result"] = op["fn"]()
            except Exception as e:
                op["error"] = e
            op["done"].set()

    def _release_open_leases(self) -> None:
        """Drop every open migration lease (shutdown/drain-evict leak
        fix): a lease still open here belongs to a client whose export
        can no longer complete, and an unreleased lease pins its pages
        against eviction forever — the final audit would rightly call
        that a leak."""
        if self._prefix is None or not self._mig_leases:
            return
        for lease_id in list(self._mig_leases):
            lease = self._mig_leases.pop(lease_id)
            try:
                self._prefix.lease_release(lease["pages"])
            except Exception:
                log.exception("migration lease %s did not release "
                              "cleanly during shutdown/drain", lease_id)

    def _mig_do_hot_prefixes(self, op: dict) -> List[dict]:
        out: List[dict] = []
        for path in self._prefix.hot_paths(op["max_pages"]):
            lease = self._mig_do_lease({"tokens": path["tokens"]})
            if lease is None:
                continue
            try:
                out.append(self._mig_do_export(
                    {"lease_id": lease["lease_id"], "mode": op["mode"]}))
            finally:
                self._mig_do_release({"lease_id": lease["lease_id"]})
        return out

    # Dispatched-but-unemitted entries: enough to keep the device and
    # the fetch pipe full; budget gating bounds per-slot run-ahead.
    _PIPELINE_DEPTH = 6

    def _loop(self):
        try:
            if self._mesh is not None:
                # Ambient mesh for the whole engine thread: program
                # traces (incl. the model's shard_map'd tp attention)
                # happen on first dispatch, in here.
                with self._mesh:
                    self._loop_body()
                return
            self._loop_body()
        except BaseException as e:  # engine crash — fail every client
            self._stopped.set()
            # The conftest deep-audit fixture skips crashed engines: a
            # loop that died mid-dispatch legitimately strands
            # allocator state, which is not a leak regression.
            self._crashed = True
            self._fetchq.put(None)  # release the fetcher thread too
            with self._mig_lock:  # release migration-op waiters too
                mig_ops, self._mig_ops = self._mig_ops, []
            for op in mig_ops:
                op["error"] = RuntimeError(
                    f"engine crashed before migration op "
                    f"{op['kind']!r} ran: {e!r}")
                op["done"].set()
            with self._audit_lock:  # release doctor() waiters too
                audit_ops, self._audit_ops = self._audit_ops, []
            for op in audit_ops:
                op["error"] = RuntimeError(
                    f"engine crashed before audit ran: {e!r}")
                op["done"].set()
            err = RuntimeError(f"LLM engine loop crashed: {e!r}")
            err.__cause__ = e
            failing = list(self._slot_req.values())
            failing += list(self._admitting)
            failing += list(self._backlog)
            failing += [st["req"] for st in self._prefilling]
            while True:
                try:
                    failing.append(self._waiting.get_nowait())
                except queue.Empty:
                    break
            seen = set()
            for req in failing:
                if id(req) in seen:
                    continue  # _admitting can overlap _slot_req
                seen.add(id(req))
                try:
                    # FAILED terminal accounting (ring + counters +
                    # spans) — best-effort: the crash itself must win.
                    if req.finished_at is None:
                        req.finished_at = time.monotonic()
                    self._observe_request(req, state=_reqev.FAILED,
                                          cause=repr(e))
                except Exception:
                    pass
                req.stream.put(err)
            raise
        finally:
            self._clock.retire()

    def _loop_body(self):
        clock = self._clock
        while not self._stopped.is_set():
            clock.begin()
            with tracing.span("llm.loop", record=False) as span:
                seq = self._loop_iteration()
                cpu_us = int(clock.cpu_spent() * 1e6)
                if seq is not None:
                    span.set(seq=seq, cpu_us=cpu_us)
                else:
                    span.set(cpu_us=cpu_us)
            # a stall is reported near the step this iteration
            # dispatched, else the last one dispatched
            clock.end(seq if seq is not None else self._steps)
        # Clean stop: drain queued migration ops exactly like the crash
        # path does, so their waiters get an immediate "engine stopped"
        # instead of hanging until their timeout expires.
        with self._mig_lock:
            mig_ops, self._mig_ops = self._mig_ops, []
        for op in mig_ops:
            op["error"] = RuntimeError(
                f"engine stopped before migration op {op['kind']!r} ran")
            op["done"].set()
        # Shutdown leak fix: a clean stop releases every open
        # migration lease and every still-occupied slot (returning its
        # pages, adapter borrow, draft pages and borrowed prefix
        # pages) BEFORE the final deep audit, so clean shutdown is
        # provably leak-free — anything the audit still finds is a
        # real accounting bug, not an artifact of stopping mid-flight.
        self._release_open_leases()
        leftovers = set(self._slot_req)
        leftovers.update(st["slot"] for st in self._prefilling)
        self._prefilling.clear()
        for slot in sorted(leftovers):
            self._release_slot(slot)
        self._process_audits()  # queued doctor() ops still get served
        try:
            self._auditor.run(deep=True)
        except Exception:
            log.exception("final shutdown audit failed")

    def _loop_iteration(self) -> Optional[int]:
        """One iteration of the engine loop, every stretch of it inside
        a phase of the loop clock (``llm.control``, ``llm.admit``,
        ``llm.pack``/``llm.dispatch``/``llm.commit``, ``llm.emit``,
        ``llm.wait`` with steps in flight and nothing to dispatch,
        ``llm.idle`` with no request anywhere in the engine).  Returns
        the ``seq`` of the step it dispatched, or None."""
        clock = self._clock
        with clock.phase("control"):
            self._process_cancels()
            self._process_drain()
            self._process_migrations()
            self._process_audits()
            backlog = self._backlog or self._prefilling
            idle = (not self._slot_req and self._waiting.empty()
                    and not backlog and self._unprocessed == 0)
            if idle:
                # Idle: settle the incremental audit debt, and
                # opportunistically run the rate-limited deep audit —
                # idle is the one time a full walk costs nobody
                # latency.
                self._auditor.maybe_incremental()
                if not self._draining.is_set():
                    self._auditor.maybe_idle_deep(time.monotonic())
        if idle:
            with clock.phase("idle"):
                self._work.wait(timeout=0.05)
                self._work.clear()
            return None
        self._process_fetched(block=False)
        with clock.phase("admit"):
            self._admit()
        with clock.phase("control"):
            self._auditor.maybe_incremental()
        steps_before = self._steps
        dispatched = False
        if self._ragged:
            if ((self._slot_req or self._prefilling)
                    and self._unprocessed < self._PIPELINE_DEPTH):
                dispatched = self._dispatch_ragged_step()
        else:
            if (self._prefilling
                    and self._unprocessed < self._PIPELINE_DEPTH):
                # One incremental-prefill chunk per iteration rides
                # the device queue BETWEEN decode chunks: running
                # streams stall at most one chunk per long-prompt
                # segment.
                with clock.phase("dispatch"):
                    self._dispatch_prefill_chunk()
                dispatched = True
            if (self._slot_req
                    and self._unprocessed < self._PIPELINE_DEPTH):
                chunk = self._chunk_size()
                if chunk > 0:
                    with clock.phase("dispatch"):
                        self._dispatch_decode(chunk)
                    dispatched = True
        if not dispatched and self._unprocessed > 0:
            # Nothing to dispatch — wait for the fetcher.
            self._process_fetched(block=True)
        return self._steps if self._steps != steps_before else None
