"""Typed configuration tree for the whole framework.

The reference scatters ~60 env-aliased pydantic-settings fields plus ad-hoc
``os.getenv`` at use sites (/root/reference/src/utils/settings.py:27-191,
retrievers/factory.py:35-48). Here there is ONE typed tree, built once from
the environment via :func:`Settings.from_env`, with the reference's env names
kept as aliases so existing deployments carry over — plus a TPU section the
reference never needed (mesh shape, dtype, KV paging, batching deadline).

No pydantic dependency at this layer: plain dataclasses keep import cost ~0
and make the tree trivially picklable into worker processes.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, replace
from typing import Optional, Sequence

__all__ = [
    "ChunkingConfig",
    "RetrievalConfig",
    "RerankConfig",
    "GeneratorConfig",
    "EmbedderConfig",
    "MeshConfig",
    "ServeConfig",
    "CacheConfig",
    "AuthConfig",
    "ObservabilityConfig",
    "Settings",
    "get_settings",
    "set_settings",
]


def _env_str(names: Sequence[str], default: str) -> str:
    for name in names:
        value = os.environ.get(name)
        if value is not None and value != "":
            return value
    return default


def _env_int(names: Sequence[str], default: int) -> int:
    raw = _env_str(names, "")
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        return default


def _env_float(names: Sequence[str], default: float) -> float:
    raw = _env_str(names, "")
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def _env_bool(names: Sequence[str], default: bool) -> bool:
    raw = _env_str(names, "").strip().lower()
    if not raw:
        return default
    return raw in ("1", "true", "yes", "on")


@dataclass
class ChunkingConfig:
    """Splitter settings (reference: chunking/text_splitter.py:23-80)."""

    strategy: str = "recursive"  # recursive | fixed | sentence
    chunk_size: int = 512
    chunk_overlap: int = 64

    @classmethod
    def from_env(cls) -> "ChunkingConfig":
        return cls(
            strategy=_env_str(["CHUNKING_STRATEGY"], "recursive"),
            chunk_size=_env_int(["CHUNK_SIZE"], 512),
            chunk_overlap=_env_int(["CHUNK_OVERLAP"], 64),
        )


@dataclass
class RetrievalConfig:
    """Retriever strategy + fusion knobs (reference: retrievers/factory.py:21-196)."""

    strategy: str = "hybrid"  # dense | bm25 | hybrid
    top_k: int = 10
    rrf_k: int = 60
    fusion_method: str = "rrf"  # rrf | weighted_rrf | comb_sum
    dense_weight: float = 0.7
    sparse_weight: float = 0.3
    # scorer plugin stack (reference default weights 0.8/0.2/0.5, factory.py:64-80)
    use_scorers: bool = False
    keyword_scorer_weight: float = 0.8
    recency_scorer_weight: float = 0.2
    mmr_scorer_weight: float = 0.5
    mmr_lambda: float = 0.7
    # BM25 parameters (Okapi defaults; pyserini used k1=0.9 b=0.4 at scale)
    bm25_k1: float = 1.5
    bm25_b: float = 0.75
    bm25_backend: str = "auto"  # auto | numpy | native
    # dense index
    index_backend: str = "tpu"  # tpu | qdrant
    collection_name: str = "sentio"
    qdrant_url: str = "http://localhost:6333"
    qdrant_api_key: str = ""
    # persisted TpuDenseIndex to load at startup ("" = start empty); BM25
    # rehydrates from the loaded documents
    index_path: str = ""
    # persisted cached-web-results index consulted before fusion (reference
    # CACHE_COLLECTION_NAME "web_cache", hybrid.py:96-107 there); "" = off
    web_cache_path: str = ""

    @classmethod
    def from_env(cls) -> "RetrievalConfig":
        return cls(
            strategy=_env_str(["RETRIEVAL_STRATEGY", "RETRIEVER_TYPE"], "hybrid"),
            top_k=_env_int(["RETRIEVAL_TOP_K", "TOP_K"], 10),
            rrf_k=_env_int(["RRF_K"], 60),
            fusion_method=_env_str(["FUSION_METHOD", "HYBRID_FUSION"], "rrf"),
            dense_weight=_env_float(["DENSE_WEIGHT"], 0.7),
            sparse_weight=_env_float(["SPARSE_WEIGHT"], 0.3),
            use_scorers=_env_bool(["USE_SCORERS"], False),
            keyword_scorer_weight=_env_float(["KEYWORD_SCORER_WEIGHT"], 0.8),
            recency_scorer_weight=_env_float(["RECENCY_SCORER_WEIGHT"], 0.2),
            mmr_scorer_weight=_env_float(["MMR_SCORER_WEIGHT"], 0.5),
            mmr_lambda=_env_float(["MMR_LAMBDA"], 0.7),
            bm25_k1=_env_float(["BM25_K1"], 1.5),
            bm25_b=_env_float(["BM25_B"], 0.75),
            bm25_backend=_env_str(["BM25_BACKEND"], "auto"),
            index_backend=_env_str(["INDEX_BACKEND", "VECTOR_STORE"], "tpu"),
            collection_name=_env_str(["COLLECTION_NAME", "QDRANT_COLLECTION"], "sentio"),
            qdrant_url=_env_str(["QDRANT_URL"], "http://localhost:6333"),
            qdrant_api_key=_env_str(["QDRANT_API_KEY"], ""),
            index_path=_env_str(["INDEX_PATH"], ""),
            web_cache_path=_env_str(["WEB_CACHE_PATH", "CACHE_COLLECTION_PATH"], ""),
        )


@dataclass
class RerankConfig:
    """Reranker selection (reference: rerankers/__init__.py:11-30, jina_reranker.py)."""

    enabled: bool = True
    kind: str = "cross_encoder"  # cross_encoder | passthrough
    top_k: int = 5
    max_pair_tokens: int = 512
    batch_size: int = 32
    # converted checkpoint (cli convert cross-encoder ...)
    checkpoint_path: str = ""
    tokenizer_path: str = ""

    @classmethod
    def from_env(cls) -> "RerankConfig":
        return cls(
            enabled=_env_bool(["USE_RERANKER"], True),
            kind=_env_str(["RERANKER_KIND", "RERANKER_TYPE"], "cross_encoder"),
            top_k=_env_int(["RERANK_TOP_K"], 5),
            max_pair_tokens=_env_int(["RERANK_MAX_PAIR_TOKENS"], 512),
            batch_size=_env_int(["RERANK_BATCH_SIZE"], 32),
            checkpoint_path=_env_str(["RERANKER_CHECKPOINT"], ""),
            tokenizer_path=_env_str(["RERANKER_TOKENIZER"], ""),
        )


@dataclass
class EmbedderConfig:
    """Bi-encoder settings. ``provider='tpu'`` is the in-process Flax model;
    ``'hash'`` is the deterministic offline fake (the reference's mock-mode
    pattern, jina.py:141-159 there) used by tests and no-hardware dev."""

    provider: str = "tpu"  # tpu | hash
    dim: int = 1024
    max_tokens: int = 512
    batch_size: int = 128
    cache_size: int = 10_000
    cache_ttl_s: float = 3600.0
    model_preset: str = "base"  # tiny | base (tiny = CPU-test scale)
    # converted checkpoint (cli convert encoder ...); "" = random-init preset
    checkpoint_path: str = ""
    tokenizer_path: str = ""  # local HF tokenizer dir (usually the HF src dir)
    # coalesce concurrent single-query embeds into one device batch
    coalesce: bool = True
    coalesce_deadline_ms: float = 5.0
    coalesce_max: int = 16

    @classmethod
    def from_env(cls) -> "EmbedderConfig":
        return cls(
            provider=_env_str(["EMBEDDER_PROVIDER", "EMBEDDING_PROVIDER"], "tpu"),
            dim=_env_int(["EMBEDDING_DIM"], 1024),
            max_tokens=_env_int(["EMBED_MAX_TOKENS"], 512),
            batch_size=_env_int(["EMBED_BATCH_SIZE"], 128),
            cache_size=_env_int(["EMBEDDING_CACHE_SIZE"], 10_000),
            cache_ttl_s=_env_float(["EMBEDDING_CACHE_TTL"], 3600.0),
            model_preset=_env_str(["EMBEDDER_PRESET"], "base"),
            checkpoint_path=_env_str(["EMBEDDER_CHECKPOINT"], ""),
            tokenizer_path=_env_str(["EMBEDDER_TOKENIZER"], ""),
            coalesce=_env_bool(["EMBED_COALESCE"], True),
            coalesce_deadline_ms=_env_float(["EMBED_COALESCE_DEADLINE_MS"], 5.0),
            coalesce_max=_env_int(["EMBED_COALESCE_MAX"], 16),
        )


@dataclass
class GeneratorConfig:
    """Generator/verifier settings (reference: llm/factory.py:14-69,
    graph/factory.py:90,145 — context budget 2000 tok, 1024 max new)."""

    provider: str = "tpu"  # tpu | echo (deterministic fake) | openai (remote API)
    model_preset: str = "llama3-8b"  # llama3-8b | tiny
    checkpoint_path: str = ""  # converted checkpoint (cli convert llama ...)
    tokenizer_path: str = ""  # local HF tokenizer dir
    # speculative decoding: a small same-vocab draft checkpoint turns each
    # decode tick into draft/verify rounds (runtime/paged_spec.py: greedy
    # rows bit-exact, sampled rows marginally exact); empty = disabled
    draft_checkpoint_path: str = ""
    speculative_k: int = 4
    # remote OpenAI-compatible endpoint (provider="openai" — the reference's
    # primary path, kept here as the pluggable fallback seam)
    api_base: str = ""
    api_key: str = ""
    api_model: str = "default"
    api_timeout_s: float = 60.0
    mode: str = "balanced"  # fast | balanced | quality | creative
    max_new_tokens: int = 1024
    context_token_budget: int = 2000
    max_prompt_tokens: int = 4096
    use_verifier: bool = True
    verifier_max_tokens: int = 512
    # confidence-gated / async verification (ops/confidence.py):
    #   sync  — verify blocks the response (the reference behavior);
    #   async — the answer returns immediately, verify runs detached and
    #           the verdict lands on the flight record (/debug/flight/{id};
    #           SSE streams get a trailing `verify` event after done);
    #   gated — confidence >= verify_confidence_threshold short-circuits
    #           with a typed `skipped_confident` verdict (zero verify
    #           decode); below-threshold requests take the async path
    verify_mode: str = "sync"  # sync | async | gated
    verify_confidence_threshold: float = 0.75
    dtype: str = "bfloat16"
    kv_page_size: int = 128
    kv_max_pages_per_seq: int = 64
    # "int8" stores KV pages quantized (per-vector absmax scales): ~half the
    # pool HBM and decode-read bandwidth, at ~1 percent attention-score error
    kv_quant: str = "none"
    # automatic radix prefix cache (runtime/radix.py): every admission
    # longest-prefix-matches against cached KV page runs and prefills only
    # its unmatched suffix; PREFIX_CACHE=0 restores plain whole-prompt
    # admission byte-for-byte
    prefix_cache: bool = True
    # a decoder with Mamba layers (models/nemotron_h.py): the states the
    # prefix cache may keep, each a whole model's state at one page boundary
    # (12.8 MB at 6 Mamba layers of the published widths); the pool is that
    # many, allocated once. Ignored by every other family
    ssm_snapshots: int = 64
    max_batch_size: int = 8
    # decode sub-steps fused into one device dispatch per engine tick —
    # amortizes host round trips; admission waits at most one tick. With an
    # empty queue the engine grows ticks toward the max so long generations
    # cost few host fetches (each per-tick fetch blocks on the device)
    decode_steps_per_tick: int = 16
    decode_max_tick_steps: int = 64
    # 2 = dispatch tick N+1 before fetching tick N (host round trip overlaps
    # device compute; results lag one tick). 1 = synchronous ticks.
    decode_pipeline_depth: int = 2
    # chunked prefill: prompts longer than this admit one page-aligned
    # segment per tick so a long (4-8K) prefill never stalls other slots'
    # decode for its full length. 0 = off (whole-prompt admission).
    prefill_chunk: int = 0
    prefill_buckets: tuple[int, ...] = (256, 512, 1024, 2048, 4096)
    temperature_by_mode: tuple[tuple[str, float], ...] = (
        ("fast", 0.0),
        ("balanced", 0.3),
        ("quality", 0.2),
        ("creative", 0.7),
    )

    def temperature(self, mode: Optional[str] = None) -> float:
        table = dict(self.temperature_by_mode)
        return table.get(mode or self.mode, 0.3)

    @classmethod
    def from_env(cls) -> "GeneratorConfig":
        return cls(
            provider=_env_str(["LLM_PROVIDER", "CHAT_LLM_PROVIDER"], "tpu"),
            model_preset=_env_str(["LLM_MODEL", "CHAT_LLM_MODEL"], "llama3-8b"),
            checkpoint_path=_env_str(["LLM_CHECKPOINT", "MODEL_PATH"], ""),
            tokenizer_path=_env_str(["LLM_TOKENIZER", "TOKENIZER_PATH"], ""),
            draft_checkpoint_path=_env_str(["LLM_DRAFT_CHECKPOINT"], ""),
            speculative_k=_env_int(["SPECULATIVE_K"], 4),
            api_base=_env_str(["OPENAI_BASE_URL", "CHAT_LLM_BASE_URL"], ""),
            api_key=_env_str(["OPENAI_API_KEY", "CHAT_LLM_API_KEY"], ""),
            api_model=_env_str(["OPENAI_MODEL", "CHAT_LLM_API_MODEL"], "default"),
            api_timeout_s=_env_float(["OPENAI_TIMEOUT_S"], 60.0),
            mode=_env_str(["LLM_MODE"], "balanced"),
            max_new_tokens=_env_int(["LLM_MAX_TOKENS", "MAX_NEW_TOKENS"], 1024),
            context_token_budget=_env_int(["CONTEXT_TOKEN_BUDGET"], 2000),
            max_prompt_tokens=_env_int(["MAX_PROMPT_TOKENS"], 4096),
            use_verifier=_env_bool(["USE_VERIFIER"], True),
            verifier_max_tokens=_env_int(["VERIFIER_MAX_TOKENS"], 512),
            verify_mode=_env_str(["VERIFY_MODE"], "sync"),
            verify_confidence_threshold=_env_float(
                ["VERIFY_CONFIDENCE_THRESHOLD"], 0.75
            ),
            dtype=_env_str(["LLM_DTYPE"], "bfloat16"),
            kv_page_size=_env_int(["KV_PAGE_SIZE"], 128),
            kv_max_pages_per_seq=_env_int(["KV_MAX_PAGES_PER_SEQ"], 64),
            kv_quant=_env_str(["KV_QUANT"], "none"),
            prefix_cache=_env_bool(["PREFIX_CACHE"], True),
            ssm_snapshots=_env_int(["SSM_SNAPSHOTS"], 64),
            max_batch_size=_env_int(["LLM_MAX_BATCH"], 8),
            decode_steps_per_tick=_env_int(["DECODE_STEPS_PER_TICK"], 16),
            decode_max_tick_steps=_env_int(["DECODE_MAX_TICK_STEPS"], 64),
            decode_pipeline_depth=_env_int(["DECODE_PIPELINE_DEPTH"], 2),
            prefill_chunk=_env_int(["PREFILL_CHUNK"], 0),
        )


@dataclass
class MeshConfig:
    """TPU mesh geometry. Axes: ``dp`` (data/batch over ICI), ``tp`` (tensor
    sharding of model weights), ``sp`` (sequence/context parallel), ``pp``
    (pipeline stages over layers), ``ep`` (expert parallel for MoE layers).
    A zero means "infer from available devices" (all devices on dp unless
    tp_size set). Multi-slice deployments add a leading ``dcn`` data axis."""

    dp_size: int = 0
    tp_size: int = 1
    sp_size: int = 1
    pp_size: int = 1
    ep_size: int = 1
    dcn_size: int = 1
    backend: str = ""  # "" = jax default; "cpu" to force host platform

    @classmethod
    def from_env(cls) -> "MeshConfig":
        return cls(
            dp_size=_env_int(["MESH_DP"], 0),
            tp_size=_env_int(["MESH_TP"], 1),
            sp_size=_env_int(["MESH_SP"], 1),
            pp_size=_env_int(["MESH_PP"], 1),
            ep_size=_env_int(["MESH_EP"], 1),
            dcn_size=_env_int(["MESH_DCN"], 1),
            backend=_env_str(["MESH_BACKEND"], ""),
        )


@dataclass
class ServeConfig:
    """HTTP serving surface (reference: api/app.py:81-101, 250-281)."""

    host: str = "0.0.0.0"
    port: int = 8000
    # per-IP sliding-window limits (reference: 10/min /embed, 100/min rest)
    rate_limit_embed_per_min: int = 10
    rate_limit_default_per_min: int = 100
    max_question_chars: int = 2000
    max_embed_chars: int = 50_000
    top_k_max: int = 20
    cors_origins: str = "*"
    # only honor X-Forwarded-For when deployed behind a trusted proxy
    trust_proxy_headers: bool = False
    # request coalescing for the TPU batcher
    batch_deadline_ms: float = 8.0
    batch_max_size: int = 8
    # /upload multipart body cap (binary documents: pdf/docx)
    max_upload_mb: int = 32
    # overload & deadline controls for the paged decode service:
    # default per-request deadline (ms) applied when the caller sends none
    # (X-Deadline-Ms header / deadline_ms body field); 0 = no default
    default_deadline_ms: float = 0.0
    # admission bound on waiting decode work (inbox + admitted); 0 = derive
    # from the engine (max(8 * max_slots, 64))
    admission_max_queue: int = 0
    # crash containment: requeues granted per request after a failed decode
    # tick whose engine reset succeeded
    crash_retry_budget: int = 1
    # graceful-shutdown drain window: in-flight requests get this long to
    # finish after the server stops admitting
    drain_deadline_s: float = 10.0
    # ---- multi-replica serving tier (runtime/replica.py) ----
    # number of independent engine+service replicas behind the router;
    # 1 = today's single-engine behavior. On a mesh, replicas map onto
    # slices of the dp axis (REPLICAS must divide MESH_DP).
    replicas: int = 1
    # replica isolation tier: "thread" (default — N engine+service+pump
    # replicas inside this process, byte-compatible with every pre-process
    # behavior) or "process" (each replica is a spawned WORKER PROCESS
    # running its own engine+service+pump behind a thin RPC shim,
    # runtime/worker.py — a replica death is a real OS process death, and
    # N pumps stop contending for one GIL)
    replica_mode: str = "thread"
    # radix-affinity stickiness: a prefix-hit replica keeps the request
    # while its backlog <= stickiness x its slot count; 0 = pure
    # least-loaded routing
    affinity_stickiness: float = 4.0
    # prompt-head tokens the router matches against each replica's radix
    # cache (longer prefixes still fully reuse inside the replica)
    route_prefix_tokens: int = 512
    # per-tenant WFQ: "tenantA:4,tenantB:1" weight overrides; unlisted
    # tenants get the default weight
    tenant_weights: str = ""
    tenant_default_weight: float = 1.0
    # token-weighted deficit counters: refill rate per unit weight
    # (0 = quota-only fairness, the deterministic default) and burst cap
    tenant_refill_tokens_per_s: float = 0.0
    tenant_burst_tokens: int = 8192
    # queue slots no single tenant's quota may consume (landing room for
    # new tenants); <0 = derive max(1, capacity // 8)
    tenant_headroom: int = -1
    # batch-priority tier sheds once total pending crosses this fraction
    # of the set's capacity (interactive may use the full capacity)
    batch_shed_fraction: float = 0.8
    # ---- replica failure domains (supervision / breaker / rebuild) ----
    # arm the per-set supervisor thread (health state machine + in-place
    # rebuild of quarantined replicas); 0 only for debugging
    replica_supervise: bool = True
    # supervisor poll cadence: breaker evaluation + rebuild scheduling
    replica_probe_interval_s: float = 0.25
    # per-replica breaker: sliding window for both the caller-observed
    # error rate and the tick-failure burst count
    replica_breaker_window_s: float = 30.0
    # quarantine when failures/samples >= rate with at least min samples
    replica_breaker_error_rate: float = 0.5
    replica_breaker_min_samples: int = 4
    # quarantine on this many failed decode ticks inside the window
    replica_breaker_tick_failures: int = 3
    # base backoff between FAILED rebuild attempts (doubles per failure,
    # capped at 60s; the first rebuild try after quarantine is immediate)
    replica_quarantine_backoff_s: float = 0.5
    # failed rebuild attempts beyond this budget idle at the max backoff
    replica_rebuild_budget: int = 3
    # grace given to an error-rate-quarantined (still working) replica's
    # in-flight requests before its rebuild swaps the service out
    replica_rebuild_drain_s: float = 5.0
    # ReplicaSet-layer failover retries per request after a replica dies
    # under it (PR 5's crash retry budget, lifted across replicas)
    replica_failover_budget: int = 1
    # resume-by-replay budget for DELIVERED-token streams (mid-flight
    # failover: the delivered prefix replays onto a survivor and decode
    # continues from the splice point): -1 follows the failover budget,
    # 0 disables resumption and keeps the typed mid-stream error
    stream_resume_budget: int = -1
    # ---- stall detection & watchdog ----
    # wall-clock budget one pump loop iteration may take before the
    # watchdog declares the replica STALLED (heartbeat stale with pending
    # work) and quarantines it — must comfortably exceed the slowest
    # legitimate tick INCLUDING a cold XLA compile; 0 disables
    tick_stall_budget_s: float = 120.0
    # watchdog stand-down bound for a replica's WARMING phase: a wedge
    # DURING warmup quarantines (typed, supervisor-visible) once warmup
    # has run this long, instead of hanging the spawn/rebuild path until
    # caller timeouts fire; 0 = warmup exempt forever (pre-budget behavior)
    warmup_budget_s: float = 600.0
    # bounded rebuild worker pool: detection cadence stays at the
    # supervisor's probe interval while rebuilds (seconds-to-minutes of
    # drain + compile, or wedged entirely) run on workers; 0 = rebuild on
    # the supervisor thread (pre-pool behavior)
    replica_rebuild_workers: int = 1
    # SSE liveness: emit a comment keepalive when no event has been
    # written for this long (a stalled decode otherwise looks identical
    # to a slow one from the client side); 0 disables
    sse_keepalive_s: float = 15.0
    # ---- multi-host worker tier (REPLICA_MODE=socket) ----
    # advertised remote workers "host:port,host:port" the router DIALS —
    # one replica per address (overrides REPLICAS); empty = spawn local
    # socket workers that self-register against the router's listener
    replica_workers: str = ""
    # shared secret for the versioned registration handshake. Spawned-
    # local mode generates a per-process random token when empty; the
    # dial-out mode (REPLICA_WORKERS) REQUIRES an explicit token set
    # identically on both sides (the worker was started elsewhere)
    socket_auth_token: str = ""
    # worker-registry listener bind (self-registering workers dial this;
    # bind a routable interface for workers on other hosts)
    socket_bind_host: str = "127.0.0.1"
    socket_bind_port: int = 0
    # transport-liveness budget: NO frames from a worker for this long
    # (status frames flow at ~100 ms) latches the typed partition death —
    # the socket analogue of proc.is_alive() going false
    socket_partition_timeout_s: float = 2.0
    # frame codec bounds: an oversized frame is refused typed on both
    # sides; a partial frame (or a write the peer stopped draining) past
    # the timeout drops the connection instead of hanging a reader
    socket_frame_max_bytes: int = 32 * 1024 * 1024
    socket_frame_timeout_s: float = 30.0
    # rebuild grace in which a live, link-partitioned worker may
    # re-register (HEAL — keeps the process and its warm engine) before
    # the supervisor reaps and respawns
    socket_heal_grace_s: float = 5.0
    # fleet telemetry plane: cadence at which a process/socket worker ships
    # its metrics-registry deltas + duty snapshot over the RPC link as
    # low-priority `telemetry` frames (0 disables — the RPC hot path is
    # then byte-identical to the pre-telemetry protocol)
    telemetry_interval_s: float = 1.0
    # elastic fleet: duty-cycle autoscaler (inert by default — the
    # registry still accepts elastic joins/deregisters either way; these
    # knobs only govern the policy loop that ACTS on the load signal)
    autoscale: bool = False
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 4
    autoscale_window_s: float = 15.0
    autoscale_out_busy: float = 0.75
    autoscale_in_busy: float = 0.15
    autoscale_out_backlog: float = 0.5
    autoscale_out_cooldown_s: float = 30.0
    autoscale_in_cooldown_s: float = 60.0
    autoscale_poll_interval_s: float = 1.0

    @classmethod
    def from_env(cls) -> "ServeConfig":
        return cls(
            host=_env_str(["SENTIO_HOST", "API_HOST", "HOST"], "0.0.0.0"),
            port=_env_int(["SENTIO_PORT", "API_PORT", "PORT"], 8000),
            rate_limit_embed_per_min=_env_int(
                ["RATE_LIMIT_EMBED_PER_MIN", "RATE_LIMIT_EMBED"], 10
            ),
            rate_limit_default_per_min=_env_int(
                ["RATE_LIMIT_DEFAULT_PER_MIN", "RATE_LIMIT_DEFAULT"], 100
            ),
            max_question_chars=_env_int(["MAX_QUESTION_CHARS"], 2000),
            max_embed_chars=_env_int(["MAX_EMBED_CHARS"], 50_000),
            top_k_max=_env_int(["TOP_K_MAX"], 20),
            cors_origins=_env_str(["CORS_ORIGINS"], "*"),
            trust_proxy_headers=_env_bool(["TRUST_PROXY_HEADERS"], False),
            batch_deadline_ms=_env_float(["BATCH_DEADLINE_MS"], 8.0),
            batch_max_size=_env_int(["BATCH_MAX_SIZE"], 8),
            max_upload_mb=_env_int(["MAX_UPLOAD_MB"], 32),
            default_deadline_ms=_env_float(["DEADLINE_MS", "DEFAULT_DEADLINE_MS"], 0.0),
            admission_max_queue=_env_int(["ADMISSION_MAX_QUEUE"], 0),
            crash_retry_budget=_env_int(["CRASH_RETRY_BUDGET"], 1),
            drain_deadline_s=_env_float(["DRAIN_DEADLINE_S"], 10.0),
            replicas=_env_int(["REPLICAS", "SENTIO_REPLICAS"], 1),
            replica_mode=_env_str(["REPLICA_MODE"], "thread").strip().lower(),
            affinity_stickiness=_env_float(["AFFINITY_STICKINESS"], 4.0),
            route_prefix_tokens=_env_int(["ROUTE_PREFIX_TOKENS"], 512),
            tenant_weights=_env_str(["TENANT_WEIGHTS"], ""),
            tenant_default_weight=_env_float(["TENANT_DEFAULT_WEIGHT"], 1.0),
            tenant_refill_tokens_per_s=_env_float(
                ["TENANT_REFILL_TOKENS_PER_S"], 0.0
            ),
            tenant_burst_tokens=_env_int(["TENANT_BURST_TOKENS"], 8192),
            tenant_headroom=_env_int(["TENANT_HEADROOM"], -1),
            batch_shed_fraction=_env_float(["BATCH_SHED_FRACTION"], 0.8),
            replica_supervise=_env_bool(["REPLICA_SUPERVISE"], True),
            replica_probe_interval_s=_env_float(
                ["REPLICA_PROBE_INTERVAL_S"], 0.25
            ),
            replica_breaker_window_s=_env_float(
                ["REPLICA_BREAKER_WINDOW_S"], 30.0
            ),
            replica_breaker_error_rate=_env_float(
                ["REPLICA_BREAKER_ERROR_RATE"], 0.5
            ),
            replica_breaker_min_samples=_env_int(
                ["REPLICA_BREAKER_MIN_SAMPLES"], 4
            ),
            replica_breaker_tick_failures=_env_int(
                ["REPLICA_BREAKER_TICK_FAILURES"], 3
            ),
            replica_quarantine_backoff_s=_env_float(
                ["REPLICA_QUARANTINE_BACKOFF_S"], 0.5
            ),
            replica_rebuild_budget=_env_int(["REPLICA_REBUILD_BUDGET"], 3),
            replica_rebuild_drain_s=_env_float(
                ["REPLICA_REBUILD_DRAIN_S"], 5.0
            ),
            replica_failover_budget=_env_int(
                ["REPLICA_FAILOVER_BUDGET"], 1
            ),
            stream_resume_budget=_env_int(["STREAM_RESUME_BUDGET"], -1),
            tick_stall_budget_s=_env_float(["TICK_STALL_BUDGET_S"], 120.0),
            warmup_budget_s=_env_float(["WARMUP_BUDGET_S"], 600.0),
            replica_rebuild_workers=_env_int(
                ["REPLICA_REBUILD_WORKERS"], 1
            ),
            sse_keepalive_s=_env_float(["SSE_KEEPALIVE_S"], 15.0),
            replica_workers=_env_str(["REPLICA_WORKERS"], ""),
            socket_auth_token=_env_str(["SOCKET_AUTH_TOKEN"], ""),
            socket_bind_host=_env_str(["SOCKET_BIND_HOST"], "127.0.0.1"),
            socket_bind_port=_env_int(["SOCKET_BIND_PORT"], 0),
            socket_partition_timeout_s=_env_float(
                ["SOCKET_PARTITION_TIMEOUT_S"], 2.0
            ),
            socket_frame_max_bytes=_env_int(
                ["SOCKET_FRAME_MAX_BYTES"], 32 * 1024 * 1024
            ),
            socket_frame_timeout_s=_env_float(
                ["SOCKET_FRAME_TIMEOUT_S"], 30.0
            ),
            socket_heal_grace_s=_env_float(["SOCKET_HEAL_GRACE_S"], 5.0),
            telemetry_interval_s=_env_float(["TELEMETRY_INTERVAL_S"], 1.0),
            autoscale=_env_bool(["AUTOSCALE"], False),
            autoscale_min_replicas=_env_int(["AUTOSCALE_MIN_REPLICAS"], 1),
            autoscale_max_replicas=_env_int(["AUTOSCALE_MAX_REPLICAS"], 4),
            autoscale_window_s=_env_float(["AUTOSCALE_WINDOW_S"], 15.0),
            autoscale_out_busy=_env_float(["AUTOSCALE_OUT_BUSY"], 0.75),
            autoscale_in_busy=_env_float(["AUTOSCALE_IN_BUSY"], 0.15),
            autoscale_out_backlog=_env_float(
                ["AUTOSCALE_OUT_BACKLOG"], 0.5
            ),
            autoscale_out_cooldown_s=_env_float(
                ["AUTOSCALE_OUT_COOLDOWN_S"], 30.0
            ),
            autoscale_in_cooldown_s=_env_float(
                ["AUTOSCALE_IN_COOLDOWN_S"], 60.0
            ),
            autoscale_poll_interval_s=_env_float(
                ["AUTOSCALE_POLL_INTERVAL_S"], 1.0
            ),
        )

    def parsed_replica_workers(self) -> list[tuple[str, int]]:
        """``"hostA:9101,hostB:9101"`` → [("hostA", 9101), ...];
        malformed entries raise (a silently dropped worker address is a
        silently smaller serving tier)."""
        out: list[tuple[str, int]] = []
        for part in self.replica_workers.split(","):
            part = part.strip()
            if not part:
                continue
            host, sep, port = part.rpartition(":")
            if not sep or not host:
                raise ValueError(
                    f"REPLICA_WORKERS entry {part!r} is not host:port")
            out.append((host, int(port)))
        return out

    def parsed_tenant_weights(self) -> dict[str, float]:
        """``"a:4,b:1"`` → {"a": 4.0, "b": 1.0}; malformed entries skipped."""
        out: dict[str, float] = {}
        for part in self.tenant_weights.split(","):
            part = part.strip()
            if not part or ":" not in part:
                continue
            name, _, raw = part.partition(":")
            try:
                out[name.strip()] = float(raw)
            except ValueError:
                continue
        return out


@dataclass
class CacheConfig:
    """Cache tiers (reference: caching/cache_manager.py:18-125)."""

    backend: str = "memory"  # memory | multi_tier (L1 + redis L2) | off
    max_entries: int = 10_000
    default_ttl_s: float = 3600.0
    query_cache_ttl_s: float = 600.0
    redis_url: str = "redis://localhost:6379/0"
    redis_key_prefix: str = "sentio:"

    @classmethod
    def from_env(cls) -> "CacheConfig":
        return cls(
            backend=_env_str(["CACHE_BACKEND"], "memory"),
            max_entries=_env_int(["CACHE_MAX_ENTRIES"], 10_000),
            default_ttl_s=_env_float(["CACHE_TTL"], 3600.0),
            query_cache_ttl_s=_env_float(["QUERY_CACHE_TTL"], 600.0),
            redis_url=_env_str(["REDIS_URL"], "redis://localhost:6379/0"),
            redis_key_prefix=_env_str(["REDIS_KEY_PREFIX"], "sentio:"),
        )


@dataclass
class AuthConfig:
    """Auth/security (reference: utils/auth.py:30-77). Disabled by default in
    dev; JWT is stdlib HMAC-SHA256."""

    enabled: bool = False
    jwt_secret: str = ""
    access_ttl_s: int = 1800
    refresh_ttl_s: int = 7 * 24 * 3600
    max_failed_attempts: int = 5
    lockout_s: int = 900
    min_password_len: int = 12

    @classmethod
    def from_env(cls) -> "AuthConfig":
        return cls(
            enabled=_env_bool(["AUTH_ENABLED"], False),
            jwt_secret=_env_str(["JWT_SECRET", "JWT_SECRET_KEY"], ""),
            access_ttl_s=_env_int(["JWT_ACCESS_TTL"], 1800),
            refresh_ttl_s=_env_int(["JWT_REFRESH_TTL"], 7 * 24 * 3600),
            max_failed_attempts=_env_int(["AUTH_MAX_FAILED"], 5),
            lockout_s=_env_int(["AUTH_LOCKOUT_S"], 900),
            min_password_len=_env_int(["AUTH_MIN_PASSWORD_LEN"], 12),
        )


@dataclass
class ObservabilityConfig:
    """Metrics, the resource monitor and where profiler windows are written
    (spans need no setting: infra/tracing.py is always on)."""

    metrics_enabled: bool = True
    monitor_interval_s: float = 30.0
    profiler_dir: str = ""  # where /debug/profile writes when the caller names no dir
    # the longest window /debug/profile traces, whatever a caller asks: a trace's export
    # takes time with the device operations it holds (a 4 s window of a routed model of
    # twelve layers, 0.9 M of them, was exported in 124 s on the chip and its caller had
    # left), so a deployment whose steps are many small operations bounds the window
    profile_max_seconds: float = 60.0

    @classmethod
    def from_env(cls) -> "ObservabilityConfig":
        return cls(
            metrics_enabled=_env_bool(["METRICS_ENABLED"], True),
            monitor_interval_s=_env_float(["MONITOR_INTERVAL_S"], 30.0),
            profiler_dir=_env_str(["JAX_PROFILER_DIR"], ""),
            profile_max_seconds=_env_float(["PROFILE_MAX_SECONDS"], 60.0),
        )


@dataclass
class Settings:
    """The whole tree. Build with :func:`Settings.from_env` once at startup;
    tests construct it directly with overrides (no env mutation needed)."""

    chunking: ChunkingConfig = field(default_factory=ChunkingConfig)
    retrieval: RetrievalConfig = field(default_factory=RetrievalConfig)
    rerank: RerankConfig = field(default_factory=RerankConfig)
    embedder: EmbedderConfig = field(default_factory=EmbedderConfig)
    generator: GeneratorConfig = field(default_factory=GeneratorConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    cache: CacheConfig = field(default_factory=CacheConfig)
    auth: AuthConfig = field(default_factory=AuthConfig)
    observability: ObservabilityConfig = field(default_factory=ObservabilityConfig)
    data_dir: str = ".sentio"

    @classmethod
    def from_env(cls) -> "Settings":
        return cls(
            chunking=ChunkingConfig.from_env(),
            retrieval=RetrievalConfig.from_env(),
            rerank=RerankConfig.from_env(),
            embedder=EmbedderConfig.from_env(),
            generator=GeneratorConfig.from_env(),
            mesh=MeshConfig.from_env(),
            serve=ServeConfig.from_env(),
            cache=CacheConfig.from_env(),
            auth=AuthConfig.from_env(),
            observability=ObservabilityConfig.from_env(),
            data_dir=_env_str(["SENTIO_DATA_DIR"], ".sentio"),
        )

    def with_overrides(self, **sections) -> "Settings":
        return replace(self, **sections)


_settings: Optional[Settings] = None


def get_settings() -> Settings:
    """Process-wide settings singleton, built lazily from the environment."""
    global _settings
    if _settings is None:
        _settings = Settings.from_env()
    return _settings


def set_settings(settings: Optional[Settings]) -> None:
    """Install (or clear, with None) the singleton — used by tests and serve
    startup to pin an explicit tree."""
    global _settings
    _settings = settings
