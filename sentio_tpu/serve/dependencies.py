"""DependencyContainer — lazy singletons for every serving component.

Parity with /root/reference/src/core/dependencies.py:24-392 (lazy component
properties, ordered ``initialize_all`` under a lock, ``cleanup``, module
singleton + accessors, ``check_dependency_health``) with the TPU-critical
inversion (SURVEY.md §3.3): the expensive state — device mesh, model
weights, corpus embeddings in HBM — is built ONCE at startup by
``initialize_all``, so the first ``/chat`` pays no model cold start. The
reference instead lazily builds its graph (and scrolls the whole Qdrant
corpus) on the first request (chat.py:38-87 there).

What that start costs is written here: ``_get`` is the one seam every
component is built through, and each build runs under a ``startup.<name>``
span of the ``startup`` flight record (infra/startup.py), a component built
inside another's ``build()`` as its child.
"""

from __future__ import annotations

import asyncio
import contextvars
import functools
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

from sentio_tpu.config import Settings, get_settings
from sentio_tpu.infra import startup

logger = logging.getLogger(__name__)

__all__ = ["DependencyContainer", "RequestThreads", "get_container", "set_container"]


class RequestThreads:
    """The threads ``/chat``'s pipelines run on, streamed and unstreamed.

    A request holds one from its first line to its last event, so the pool's
    width is how many requests can be anywhere in the server at once. It is
    what the generation service would admit before it sheds — its
    ``max_queue``, which a ``ReplicaSet``'s stats sum over its replicas — so
    a caller beyond the engine's slots waits in the engine's inbox and
    queue, where deadlines, tenant shares and the 429 of ``queue_full`` see
    it, and none waits for a thread (the request's ``pool_wait`` stage).
    asyncio's default executor, ``min(32, cores + 4)`` wide, keeps the rest:
    ingest, health probes, ``/debug/profile``. Threads start on demand."""

    THREAD_PREFIX = "sentio-request"

    def __init__(self, service: Any = None) -> None:
        width = 0
        if service is not None and hasattr(service, "stats"):
            try:
                width = int(service.stats().get("max_queue") or 0)
            except Exception:  # noqa: BLE001 — a service that cannot say keeps the default
                logger.debug("generation service gave no max_queue", exc_info=True)
        if width > 0:
            self.width, self.origin = width, "max_queue"
        else:
            # no engine to read (echo / API providers): asyncio's own width
            self.width = min(32, (os.cpu_count() or 1) + 4)
            self.origin = "default_executor"
        self._executor = ThreadPoolExecutor(
            max_workers=self.width, thread_name_prefix=self.THREAD_PREFIX)

    def run(self, fn, /, *args: Any, **kwargs: Any) -> asyncio.Future:
        """``asyncio.to_thread`` on these threads: the callable runs inside
        a copy of the caller's context, through which the stages below find
        their request (infra/tracing.py)."""
        ctx = contextvars.copy_context()
        return asyncio.get_running_loop().run_in_executor(
            self._executor, functools.partial(ctx.run, fn, *args, **kwargs))

    def close(self) -> None:
        self._executor.shutdown(wait=True, cancel_futures=True)


class DependencyContainer:
    """Every component is a lazy cached property; ``initialize_all`` forces
    construction in dependency order. Tests inject fakes via the
    constructor-style ``overrides`` mapping (the reference's
    ``dependency_overrides`` pattern, conftest there)."""

    def __init__(self, settings: Optional[Settings] = None, **overrides: Any) -> None:
        self.settings = settings or get_settings()
        self._cache: dict[str, Any] = dict(overrides)
        self._lock = threading.RLock()
        self._initialized = False

    def _get(self, name: str, build) -> Any:
        with self._lock:
            if name not in self._cache:
                with startup.phase(name):
                    self._cache[name] = build()
            return self._cache[name]

    def override(self, name: str, value: Any) -> None:
        with self._lock:
            self._cache[name] = value

    def peek(self, name: str) -> Any:
        """Already-built component or None — never constructs AND never
        blocks: initialize_all holds the container lock for the whole eager
        startup (weights onto HBM, potentially minutes), and a /metrics
        scrape waiting on it would freeze the event loop — liveness probes
        included. A plain dict read is GIL-atomic."""
        return self._cache.get(name)

    # ------------------------------------------------------------ components

    @property
    def mesh(self):
        def build():
            cfg = self.settings.mesh
            if max(cfg.dp_size, cfg.tp_size, cfg.sp_size, cfg.pp_size,
                   cfg.ep_size, cfg.dcn_size) <= 1:
                # no MESH_* axis asked for: one device, no mesh machinery.
                # More devices being VISIBLE is not a request to replicate
                # every model over them (a four-chip host used to get a
                # dp=4 mesh this way); MESH_DP=0 still means "absorb the
                # devices the other axes leave" once any axis is set.
                return None
            from sentio_tpu.parallel.mesh import build_mesh

            return build_mesh(cfg)

        return self._get("mesh", build)

    @property
    def embedder(self):
        def build():
            from sentio_tpu.ops.embedder import get_embedder

            return get_embedder(self.settings.embedder, mesh=self.mesh)

        return self._get("embedder", build)

    @property
    def dense_index(self):
        def build():
            from pathlib import Path

            from sentio_tpu.ops.dense_index import TpuDenseIndex

            cfg = self.settings.retrieval
            if cfg.index_backend != "tpu":
                # external-store escape hatch (SURVEY.md §7: corpora too
                # large for in-HBM exact search) — one construction path,
                # the registry, so config wiring can't drift
                from sentio_tpu.ops.vector_store import get_vector_store

                return get_vector_store(
                    cfg.index_backend,
                    dim=self.embedder.dimension,
                    mesh=self.mesh,
                    settings=self.settings,
                )
            path = self.settings.retrieval.index_path
            # save() writes <path>.npz + <path>.json — check the metadata file
            if path and Path(path).with_suffix(".json").exists():
                logger.info("loading dense index from %s", path)
                index = TpuDenseIndex.load(
                    path, mesh=self.mesh, dtype=self.settings.generator.dtype
                )
                want = self.embedder.dimension
                if index.dim != want:
                    raise ValueError(
                        f"persisted dense index at {path} has dim={index.dim} but the "
                        f"configured embedder produces dim={want} — re-ingest with the "
                        "current embedder or point SENTIO_INDEX_PATH elsewhere"
                    )
                return index
            return TpuDenseIndex(
                dim=self.embedder.dimension,
                mesh=self.mesh,
                dtype=self.settings.generator.dtype,
            )

        return self._get("dense_index", build)

    @property
    def sparse_index(self):
        def build():
            from sentio_tpu.ops.bm25 import BM25Params, make_bm25_index

            cfg = self.settings.retrieval
            index = make_bm25_index(
                params=BM25Params(k1=cfg.bm25_k1, b=cfg.bm25_b),
                backend=cfg.bm25_backend,
            )
            docs = self.dense_index.documents()
            if docs:  # rehydrate from a persisted dense index
                index.build(docs)
            return index

        return self._get("sparse_index", build)

    @property
    def web_cache_index(self):
        """Persisted cached-web-results collection, consulted by the hybrid
        retriever before fusion (reference's `web_cache` Qdrant collection,
        hybrid.py:96-107 there). None unless a persisted index exists."""

        def build():
            from pathlib import Path

            from sentio_tpu.ops.dense_index import TpuDenseIndex

            path = self.settings.retrieval.web_cache_path
            if not path or not Path(path).with_suffix(".json").exists():
                return None
            logger.info("loading web-cache index from %s", path)
            return TpuDenseIndex.load(
                path, mesh=self.mesh, dtype=self.settings.generator.dtype
            )

        return self._get("web_cache_index", build)

    @property
    def retriever(self):
        def build():
            from sentio_tpu.ops.retrievers import create_retriever

            return create_retriever(
                settings=self.settings,
                embedder=self.embedder,
                dense_index=self.dense_index,
                bm25_index=self.sparse_index,
                web_cache_index=self.web_cache_index,
            )

        return self._get("retriever", build)

    @property
    def reranker(self):
        def build():
            if not self.settings.rerank.enabled:
                return None
            from sentio_tpu.ops.reranker import get_reranker

            return get_reranker(self.settings.rerank.kind, config=self.settings.rerank, mesh=self.mesh)

        return self._get("reranker", build)

    @property
    def decoder(self):
        """The served decoder — weights on the device, their configuration
        and tokenizer (runtime/weights.py::load_decoder) — or None when the
        provider is not the in-process one."""

        def build():
            cfg = self.settings.generator
            if cfg.provider != "tpu":
                return None
            from sentio_tpu.runtime.weights import load_decoder

            return load_decoder(cfg, mesh=self.mesh)

        return self._get("decoder", build)

    @property
    def generation_service(self):
        """Multi-replica continuous-batching tier over the paged KV pool —
        the default decode path for /chat. A :class:`ReplicaSet` owns
        REPLICAS independent engine+service replicas (private pool, radix
        tree, and pump each; one copy of the weights and one tokenizer
        shared by all), routes by radix-prefix
        affinity then least-loaded, and applies per-tenant weighted fair
        queueing in front. REPLICAS=1 degenerates to the single-engine
        behavior every existing test pins."""

        def build():
            cfg = self.settings.generator
            serve = self.settings.serve
            decoder = self.decoder
            if decoder is None:
                return None
            from sentio_tpu.infra import tracing
            from sentio_tpu.runtime.paged import ContinuousBatchingEngine
            from sentio_tpu.runtime.replica import ReplicaSet
            from sentio_tpu.runtime.service import PagedGenerationService

            n_replicas = max(serve.replicas, 1)
            replica_mode = serve.replica_mode
            if replica_mode not in ("thread", "process", "socket"):
                # a typo must not SILENTLY degrade to the GIL-bound thread
                # tier while the operator believes they have OS-level
                # failure domains
                logger.warning(
                    "REPLICA_MODE=%r unknown (expected "
                    "thread|process|socket); using thread mode",
                    replica_mode,
                )
                replica_mode = "thread"
            if replica_mode in ("process", "socket") and self.mesh is not None:
                # per-process replicas over dp-axis mesh slices need
                # coordinated multi-process device init — the remaining
                # ROADMAP item 1 leg. Fall back rather than half-work.
                logger.warning(
                    "REPLICA_MODE=%s ignored: a device mesh is "
                    "configured (MESH_* > 1) and mesh-sliced worker "
                    "replicas are not wired yet; using thread mode",
                    replica_mode,
                )
                replica_mode = "thread"

            if (replica_mode in ("process", "socket")
                    and not (replica_mode == "socket"
                             and serve.parsed_replica_workers())):
                # workers are spawned on THIS host, and a chip belongs to
                # one process: this router already holds it (the decoder's
                # weights above, the embedder, the reranker), so a worker
                # that needs the same chip hangs in start-up until
                # warmup_budget_s. Refuse now, and say why.
                import jax

                if jax.default_backend() == "tpu":
                    from sentio_tpu.infra.exceptions import DeviceError

                    raise DeviceError(
                        f"REPLICA_MODE={replica_mode} spawns worker "
                        "processes on this host, but the router process "
                        "already holds the TPU (engine weights, embedder, "
                        "reranker) and a chip belongs to one process at a "
                        "time. Use REPLICA_MODE=thread here, or socket mode "
                        "with REPLICA_WORKERS pointing at workers that own "
                        "their chips on other hosts."
                    )

            # paged speculative decoding (runtime/paged_spec.py): a
            # configured draft checkpoint accelerates the decode tick
            draft_params = draft_cfg = None
            if cfg.draft_checkpoint_path and self.mesh is not None:
                logger.warning(
                    "LLM_DRAFT_CHECKPOINT ignored: paged speculation does "
                    "not support a device mesh yet (MESH_* > 1 configured); "
                    "/info reports this under generator.speculative"
                )
            if cfg.draft_checkpoint_path and self.mesh is None:
                if cfg.prefill_chunk:
                    logger.warning(
                        "LLM_DRAFT_CHECKPOINT ignored: PREFILL_CHUNK is set "
                        "and paged speculation requires whole-prompt "
                        "admission (the draft prefills full prompts)"
                    )
                elif replica_mode in ("process", "socket"):
                    # workers load the draft themselves (mmap-shared, via
                    # WorkerSpec below) — loading a private router-process
                    # copy here would defeat the one-copy-per-host goal
                    logger.info(
                        "paged speculation: draft %s loads in-worker (k=%d)",
                        cfg.draft_checkpoint_path, cfg.speculative_k,
                    )
                else:
                    from sentio_tpu.runtime.weights import load_model

                    draft_params, draft_cfg, _ = load_model(
                        cfg.draft_checkpoint_path, expect_family="llama"
                    )
                    logger.info(
                        "paged speculation: draft %s (dim=%d L=%d, k=%d)",
                        cfg.draft_checkpoint_path, draft_cfg.dim,
                        draft_cfg.n_layers, cfg.speculative_k,
                    )
            # replicas map onto dp-axis slices of the mesh when it divides
            # evenly; otherwise every replica shares the whole mesh (their
            # dispatches serialize on device — still correct, no scale-out)
            meshes = [self.mesh] * n_replicas
            if self.mesh is not None and n_replicas > 1:
                from sentio_tpu.parallel.mesh import MeshError, split_mesh_dp

                try:
                    meshes = split_mesh_dp(self.mesh, n_replicas)
                    logger.info(
                        "replicas mapped onto %d dp-axis mesh slices",
                        n_replicas,
                    )
                except MeshError as exc:
                    logger.warning(
                        "REPLICAS=%d cannot slice the dp axis (%s); "
                        "replicas will share the whole mesh", n_replicas, exc,
                    )

            warm_head = ""
            if cfg.prefix_cache:
                # the radix cache learns shared heads automatically from
                # traffic; warming the rendered template head (instruction +
                # section header) just spares the FIRST /chat its cold
                # prefill of that span — per replica, since each owns a
                # private tree
                from sentio_tpu.ops.prompts import PromptBuilder

                prompts = PromptBuilder()
                warm_head = prompts.static_head(
                    "retrieve", instruction=prompts.load("profile")
                ) or ""

            if replica_mode in ("process", "socket"):
                # worker replica tier (runtime/worker.py): each replica is
                # a worker process owning its private engine+service+pump;
                # the router keeps only a thin RPC shim per replica.
                # Weights are NOT shipped through the transport — each
                # worker loads the checkpoint itself, memory-mapped, so N
                # workers share one page-cache copy per host (or re-derive
                # the identical seeded random init in the no-checkpoint
                # dev mode). "process" runs the spawn-pipe transport;
                # "socket" runs the TCP transport: spawned local workers
                # self-register against the router's WorkerRegistry
                # listener, or — with REPLICA_WORKERS=host:port,... — the
                # router dials workers already serving on OTHER hosts
                # (started there via runtime.worker.worker_serve) and the
                # supervisor's rebuild duck-types to re-dial/await
                # re-registration with backoff.
                import dataclasses as _dc

                from sentio_tpu.models.families import family_of
                from sentio_tpu.runtime.worker import (
                    ProcessReplica,
                    WorkerSpec,
                )

                engine_kwargs = dict(
                    max_slots=cfg.max_batch_size,
                    page_size=cfg.kv_page_size,
                    max_pages_per_seq=cfg.kv_max_pages_per_seq,
                    steps_per_tick=cfg.decode_steps_per_tick,
                    max_tick_steps=cfg.decode_max_tick_steps,
                    pipeline_depth=cfg.decode_pipeline_depth,
                    kv_quant=cfg.kv_quant,
                    prefill_chunk=cfg.prefill_chunk or None,
                    spec_k=cfg.speculative_k,
                    prefix_cache=cfg.prefix_cache,
                    ssm_snapshots=cfg.ssm_snapshots,
                )
                service_kwargs = dict(
                    max_queue=serve.admission_max_queue or None,
                    default_deadline_s=(
                        serve.default_deadline_ms / 1e3
                        if serve.default_deadline_ms > 0 else None
                    ),
                    retry_budget=serve.crash_retry_budget,
                    tick_stall_budget_s=serve.tick_stall_budget_s,
                    warmup_budget_s=serve.warmup_budget_s,
                )
                draft_path = ""
                if cfg.draft_checkpoint_path and not cfg.prefill_chunk:
                    # the draft loads INSIDE each worker (mmap-shared);
                    # the prefill_chunk incompatibility warning above
                    # applies identically
                    draft_path = cfg.draft_checkpoint_path
                registry = None
                worker_addrs: list = []
                auth_token = ""
                if replica_mode == "socket":
                    import secrets as _secrets

                    from sentio_tpu.runtime.replica import WorkerRegistry

                    worker_addrs = serve.parsed_replica_workers()
                    if worker_addrs:
                        # advertised remote workers: one replica per
                        # address; both sides must share the explicit token
                        if not serve.socket_auth_token:
                            raise ValueError(
                                "REPLICA_WORKERS needs SOCKET_AUTH_TOKEN "
                                "set identically on router and workers"
                            )
                        n_replicas = len(worker_addrs)
                    auth_token = (serve.socket_auth_token
                                  or _secrets.token_hex(16))
                    registry = WorkerRegistry(
                        auth_token, slots=n_replicas,
                        bind_host=serve.socket_bind_host,
                        bind_port=serve.socket_bind_port,
                        max_frame_bytes=serve.socket_frame_max_bytes,
                        frame_timeout_s=serve.socket_frame_timeout_s,
                    )
                    self._cache["worker_registry"] = registry
                def make_spec(i: int) -> WorkerSpec:
                    # shared by the startup loop, the elastic-join
                    # membership source, and the autoscaler's launcher —
                    # one spec recipe, three registration paths
                    return WorkerSpec(factory_kwargs=dict(
                        model_family=family_of(decoder.model_config).name,
                        model_config=(
                            None if cfg.checkpoint_path
                            else _dc.asdict(decoder.model_config)
                        ),
                        checkpoint_path=cfg.checkpoint_path,
                        tokenizer_path=cfg.tokenizer_path,
                        draft_checkpoint_path=draft_path,
                        engine_kwargs=engine_kwargs,
                        service_kwargs={**service_kwargs,
                                        "replica_id": i},
                        warm_prefix_text=warm_head,
                    ), telemetry_interval_s=serve.telemetry_interval_s,
                       **({} if replica_mode != "socket" else dict(
                        auth_token=auth_token,
                        reconnect=True,
                        max_frame_bytes=serve.socket_frame_max_bytes,
                        frame_timeout_s=serve.socket_frame_timeout_s,
                    )))

                services = []
                try:
                    for i in range(n_replicas):
                        spec = make_spec(i)
                        transport_kwargs = (
                            {} if replica_mode != "socket" else dict(
                                transport_mode="socket",
                                registry=registry,
                                connect_addr=(worker_addrs[i]
                                              if worker_addrs else None),
                                partition_timeout_s=(
                                    serve.socket_partition_timeout_s),
                                heal_grace_s=serve.socket_heal_grace_s,
                            ))
                        services.append(ProcessReplica(
                            spec, decoder.tokenizer, replica_id=i,
                            **transport_kwargs,
                        ))
                    logger.info(
                        "%s-mode replica tier: %d workers (pids %s%s)",
                        replica_mode, n_replicas,
                        [s.pid for s in services],
                        (f", registry {registry.address}" if registry
                         else ""),
                    )
                    replica_set = ReplicaSet(
                        services,
                        tenant_weights=serve.parsed_tenant_weights(),
                        tenant_default_weight=serve.tenant_default_weight,
                        tenant_refill_tokens_per_s=(
                            serve.tenant_refill_tokens_per_s
                        ),
                        tenant_burst_tokens=serve.tenant_burst_tokens,
                        tenant_headroom=(serve.tenant_headroom
                                         if serve.tenant_headroom >= 0
                                         else None),
                        batch_shed_fraction=serve.batch_shed_fraction,
                        affinity_stickiness=serve.affinity_stickiness,
                        route_prefix_tokens=serve.route_prefix_tokens,
                        supervise=serve.replica_supervise,
                        probe_interval_s=serve.replica_probe_interval_s,
                        breaker_window_s=serve.replica_breaker_window_s,
                        breaker_error_rate=serve.replica_breaker_error_rate,
                        breaker_min_samples=(
                            serve.replica_breaker_min_samples
                        ),
                        breaker_tick_failures=(
                            serve.replica_breaker_tick_failures
                        ),
                        quarantine_backoff_s=(
                            serve.replica_quarantine_backoff_s
                        ),
                        rebuild_budget=serve.replica_rebuild_budget,
                        rebuild_drain_s=serve.replica_rebuild_drain_s,
                        failover_budget=serve.replica_failover_budget,
                        stream_resume_budget=(
                            serve.stream_resume_budget
                            if serve.stream_resume_budget >= 0 else None
                        ),
                        rebuild_workers=serve.replica_rebuild_workers,
                    )
                except BaseException:
                    # a failed spawn — or a ReplicaSet constructor reject —
                    # must not leak the workers already running: each is a
                    # live OS process holding an engine + KV pool, and
                    # _get retries this build on the next request,
                    # multiplying the leak
                    for s in services:
                        try:
                            s.close(join_timeout_s=5.0)
                        except Exception:  # noqa: BLE001 — reap best-effort
                            pass
                    if registry is not None:
                        try:
                            registry.close()
                        except Exception:  # noqa: BLE001 — best-effort
                            pass
                        self._cache.pop("worker_registry", None)
                    raise
                if registry is not None:
                    # elastic fleet: workers that hello AFTER startup with
                    # the sentinel slot -1 land on the registry's join
                    # queue; the supervisor drains it through this source
                    # and wires each one into routing/WFQ/health. Active
                    # regardless of AUTOSCALE — remote fleets scale
                    # themselves by just registering.
                    def _join_elastic():
                        joined = []
                        for slot in registry.drain_joins():
                            svc = ProcessReplica(
                                make_spec(slot), decoder.tokenizer,
                                replica_id=slot,
                                transport_mode="socket",
                                registry=registry,
                                adopt_registration=True,
                                partition_timeout_s=(
                                    serve.socket_partition_timeout_s),
                                heal_grace_s=serve.socket_heal_grace_s,
                            )
                            joined.append((slot, svc))
                        return joined

                    replica_set.set_membership_source(
                        _join_elastic, release_slot=registry.release_slot)
                if serve.autoscale:
                    from sentio_tpu.runtime.autoscaler import (
                        AutoscalePolicy, Autoscaler, socket_worker_launcher,
                    )

                    launcher = None
                    if registry is not None:
                        launcher = socket_worker_launcher(
                            registry.address, make_spec(-1))
                    autoscaler = Autoscaler(
                        replica_set,
                        AutoscalePolicy(
                            min_replicas=serve.autoscale_min_replicas,
                            max_replicas=serve.autoscale_max_replicas,
                            window_s=serve.autoscale_window_s,
                            out_busy=serve.autoscale_out_busy,
                            in_busy=serve.autoscale_in_busy,
                            out_backlog=serve.autoscale_out_backlog,
                            out_cooldown_s=serve.autoscale_out_cooldown_s,
                            in_cooldown_s=serve.autoscale_in_cooldown_s,
                        ),
                        launcher=launcher,
                        poll_interval_s=serve.autoscale_poll_interval_s,
                    )
                    autoscaler.start()
                    self._cache["autoscaler"] = autoscaler
                return replica_set

            services = []
            for i in range(n_replicas):
                paged = ContinuousBatchingEngine(
                    model_config=decoder.model_config,
                    params=decoder.params,
                    tokenizer=decoder.tokenizer,
                    max_slots=cfg.max_batch_size,
                    page_size=cfg.kv_page_size,
                    max_pages_per_seq=cfg.kv_max_pages_per_seq,
                    steps_per_tick=cfg.decode_steps_per_tick,
                    max_tick_steps=cfg.decode_max_tick_steps,
                    pipeline_depth=cfg.decode_pipeline_depth,
                    kv_quant=cfg.kv_quant,
                    prefill_chunk=cfg.prefill_chunk or None,
                    draft_params=draft_params,
                    draft_config=draft_cfg,
                    spec_k=cfg.speculative_k,
                    prefix_cache=cfg.prefix_cache,
                    ssm_snapshots=cfg.ssm_snapshots,
                    mesh=meshes[i],  # pool kv-heads shard over tp with the weights
                )
                if warm_head:
                    with tracing.span("prefix.warm") as warm:
                        shared = warm.fields["tokens"] = paged.warm_prefix(warm_head)
                    if shared and i == 0:
                        logger.info(
                            "prefix cache warmed: %d tokens of the /chat "
                            "template head (x%d replicas)", shared, n_replicas,
                        )
                services.append(PagedGenerationService(
                    paged,
                    max_queue=serve.admission_max_queue or None,
                    default_deadline_s=(
                        serve.default_deadline_ms / 1e3
                        if serve.default_deadline_ms > 0 else None
                    ),
                    retry_budget=serve.crash_retry_budget,
                    replica_id=i,
                    tick_stall_budget_s=serve.tick_stall_budget_s,
                    warmup_budget_s=serve.warmup_budget_s,
                ))
            return ReplicaSet(
                services,
                tenant_weights=serve.parsed_tenant_weights(),
                tenant_default_weight=serve.tenant_default_weight,
                tenant_refill_tokens_per_s=serve.tenant_refill_tokens_per_s,
                tenant_burst_tokens=serve.tenant_burst_tokens,
                tenant_headroom=(serve.tenant_headroom
                                 if serve.tenant_headroom >= 0 else None),
                batch_shed_fraction=serve.batch_shed_fraction,
                affinity_stickiness=serve.affinity_stickiness,
                route_prefix_tokens=serve.route_prefix_tokens,
                # replica failure domains: breaker + supervised in-place
                # rebuild + cross-replica failover (REPLICA_* env knobs)
                supervise=serve.replica_supervise,
                probe_interval_s=serve.replica_probe_interval_s,
                breaker_window_s=serve.replica_breaker_window_s,
                breaker_error_rate=serve.replica_breaker_error_rate,
                breaker_min_samples=serve.replica_breaker_min_samples,
                breaker_tick_failures=serve.replica_breaker_tick_failures,
                quarantine_backoff_s=serve.replica_quarantine_backoff_s,
                rebuild_budget=serve.replica_rebuild_budget,
                rebuild_drain_s=serve.replica_rebuild_drain_s,
                failover_budget=serve.replica_failover_budget,
                # resume-by-replay for delivered-token streams
                # (STREAM_RESUME_BUDGET; -1 follows the failover budget)
                stream_resume_budget=(
                    serve.stream_resume_budget
                    if serve.stream_resume_budget >= 0 else None
                ),
                rebuild_workers=serve.replica_rebuild_workers,
            )

        return self._get("generation_service", build)

    @property
    def generator(self):
        def build():
            from sentio_tpu.ops.generator import create_generator

            return create_generator(
                settings=self.settings,
                service=self.generation_service,
            )

        return self._get("generator", build)

    @property
    def verifier(self):
        def build():
            if not self.settings.generator.use_verifier:
                return None
            from sentio_tpu.ops.verifier import AnswerVerifier

            return AnswerVerifier(generator=self.generator, config=self.settings.generator)

        return self._get("verifier", build)

    @property
    def graph(self):
        def build():
            from sentio_tpu.graph.factory import GraphConfig, build_basic_graph

            return build_basic_graph(
                self.retriever,
                self.generator,
                reranker=self.reranker,
                verifier=self.verifier,
                config=GraphConfig.from_settings(self.settings),
            )

        return self._get("graph", build)

    @property
    def ingestor(self):
        def build():
            from sentio_tpu.ops.ingest import DocumentIngestor

            return DocumentIngestor(
                embedder=self.embedder,
                dense_index=self.dense_index,
                sparse_index=self.sparse_index,
                settings=self.settings,
            )

        return self._get("ingestor", build)

    @property
    def cache_manager(self):
        def build():
            from sentio_tpu.infra.caching import CacheManager

            return CacheManager(config=self.settings.cache)

        return self._get("cache_manager", build)

    @property
    def auth_manager(self):
        def build():
            if not self.settings.auth.enabled:
                return None
            from sentio_tpu.infra.auth import AuthManager

            return AuthManager(config=self.settings.auth)

        return self._get("auth_manager", build)

    @property
    def rate_limiter(self):
        def build():
            from sentio_tpu.infra.security import IPRateLimiter, RateLimitConfig

            limiter = IPRateLimiter(
                default=RateLimitConfig(per_minute=self.settings.serve.rate_limit_default_per_min)
            )
            limiter.configure("/embed", self.settings.serve.rate_limit_embed_per_min)
            return limiter

        return self._get("rate_limiter", build)

    @property
    def metrics(self):
        def build():
            from sentio_tpu.infra.metrics import get_metrics

            return get_metrics()

        return self._get("metrics", build)

    @property
    def request_threads(self) -> RequestThreads:
        return self._get(
            "request_threads", lambda: RequestThreads(self.generation_service))

    @property
    def chat_handler(self):
        def build():
            from sentio_tpu.serve.handlers import ChatHandler

            return ChatHandler(container=self)

        return self._get("chat_handler", build)

    @property
    def health_handler(self):
        def build():
            from sentio_tpu.serve.handlers import HealthHandler

            return HealthHandler(container=self)

        return self._get("health_handler", build)

    # ------------------------------------------------------------- lifecycle

    def initialize_all(self) -> None:
        """Eagerly build the whole stack in dependency order: mesh → models
        (weights onto HBM) → indexes → graph → handlers. Idempotent."""
        with self._lock:
            if self._initialized:
                return
            t0 = time.perf_counter()
            from sentio_tpu.infra import tracing

            # every compile from here on is timed by program (the entry
            # points register the listeners too: once a process)
            tracing.install_compile_listeners()
            with startup.phase("backend") as backend:
                # the first device enumeration starts the accelerator's
                # runtime: a phase of its own, not whichever component's
                # build touched JAX first
                import jax

                devices = jax.devices()
                backend.fields.update(platform=devices[0].platform, devices=len(devices))
            order = [
                "mesh", "embedder", "dense_index", "sparse_index", "retriever",
                "reranker", "decoder", "generation_service", "request_threads",
                "generator", "verifier", "graph", "ingestor", "cache_manager",
                "auth_manager", "rate_limiter", "metrics", "chat_handler",
                "health_handler",
            ]
            for name in order:
                getattr(self, name)
                logger.debug("container: %s ready", name)
            self._initialized = True
            logger.info("container initialized in %.1fs, %.1fs after the process started",
                        time.perf_counter() - t0, startup.uptime_s())

    def _close(self, *names: str) -> None:
        for name in names:
            component = self._cache.get(name)
            if component is not None and hasattr(component, "close"):
                try:
                    component.close()
                except Exception:  # noqa: BLE001 — shutdown is best-effort
                    logger.warning("%s close failed", name, exc_info=True)

    def cleanup(self) -> None:
        with self._lock:
            # the autoscaler stops FIRST (it must not launch or retire
            # mid-teardown)
            self._close("autoscaler", "generation_service")
        # the request threads are joined once the service's close has failed
        # the tickets they wait on, and OUTSIDE the lock: a pipeline on its
        # way out may still ask the container for a component
        self._close("request_threads")
        with self._lock:
            # worker_registry closes AFTER the generation service: the
            # ReplicaSet's close reaps workers whose re-registrations the
            # listener may still be fielding
            self._close("embedder", "worker_registry")
            self._cache.clear()
            self._initialized = False

    def check_dependency_health(self) -> dict[str, Any]:
        """DI-level health map (reference: dependencies.py:346-379 there)."""
        out: dict[str, Any] = {}
        try:
            out["dense_index"] = {"healthy": True, "size": self.dense_index.size}
        except Exception as exc:  # noqa: BLE001
            out["dense_index"] = {"healthy": False, "error": str(exc)}
        try:
            out["sparse_index"] = {"healthy": True, "size": self.sparse_index.size}
        except Exception as exc:  # noqa: BLE001
            out["sparse_index"] = {"healthy": False, "error": str(exc)}
        try:
            vec = self.embedder.embed("health probe")
            out["embedder"] = {"healthy": len(vec) == self.embedder.dimension}
        except Exception as exc:  # noqa: BLE001
            out["embedder"] = {"healthy": False, "error": str(exc)}
        try:
            from sentio_tpu.runtime.weights import device_stats

            decoder = self.decoder
            out["engine"] = (
                {"healthy": True,
                 **device_stats(self.mesh, decoder.model_config)}
                if decoder is not None
                else {"healthy": True, "provider": self.settings.generator.provider}
            )
        except Exception as exc:  # noqa: BLE001
            out["engine"] = {"healthy": False, "error": str(exc)}
        try:
            service = self.generation_service
            if service is not None:
                out["generation_service"] = {"healthy": True, **service.stats()}
        except Exception as exc:  # noqa: BLE001
            out["generation_service"] = {"healthy": False, "error": str(exc)}
        return out


_container: Optional[DependencyContainer] = None
_container_lock = threading.Lock()


def get_container() -> DependencyContainer:
    global _container
    with _container_lock:
        if _container is None:
            _container = DependencyContainer()
        return _container


def set_container(container: Optional[DependencyContainer]) -> None:
    global _container
    with _container_lock:
        _container = container
