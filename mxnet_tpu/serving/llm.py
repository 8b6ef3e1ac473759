"""``LLMEngine`` — continuous-batching autoregressive generation.

The PR-1 :class:`~mxnet_tpu.serving.engine.InferenceEngine` micro-batches
fixed-shape forward passes; autoregressive decode needs its own engine,
because the unit of scheduling is a *step*, not a request. Decode is
HBM-bandwidth bound (``benchmark/results_llm_tpu.json``: 3.3k tok/s
against a 70k tok/s roofline — 4.7% utilization): every generated token
re-reads all weights plus the KV cache, so throughput is won by filling
the batch dimension and shrinking bytes/token. Three mechanisms:

- **Paged KV-cache block pool** — the cache is a pool of fixed-size
  (block_size x heads x head_dim) blocks plus a per-lane block table;
  ``decode_step_paged`` gathers K/V through the table INSIDE the jitted
  step (:func:`~mxnet_tpu.ops.nn.paged_attention`), so the pool shape is
  static and sequence growth never retraces. int8 KV is the default
  (half the bytes of bf16 on the read path, the existing per-token
  dequant layout). Blocks return to the free list the moment a sequence
  finishes: pool capacity — not ``max_length x max_batch`` — bounds
  memory.
- **Prefill/decode disaggregation** — prompts prefill as their own
  pow2-bucketed compiled programs (the engine ladder-bucket idea applied
  to the sequence axis) whose resulting KV blocks are spliced into the
  running pool; decode runs as ONE fixed-shape program over
  ``(max_running, 1)`` with retired lanes pointed at a trash block.
- **In-flight (continuous) batching** — the scheduler admits new
  sequences into empty decode lanes every step without flushing the
  batch, layered on :mod:`.admission` deadlines/shedding, with
  EOS/length retirement and per-token streaming.

Observability: ``llm_*`` gauges/counters in the telemetry registry
(lane occupancy, pool levels, prefill-vs-decode split, tok/s — all in
the flight-recorder dump), decode/prefill steps spanned in the step
timeline (``tools/trace_view.py`` attributes them), chaos site
``serving.llm`` on the prefill-splice path, and scheduler faults typed
through the resilience transient-vs-fatal classifier.

This module is the scheduler; the pools and everything that indexes them
are :mod:`.kv_cache` (``docs/llm_serving.md``: modules, block-table
anatomy, scheduler policy).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as onp

from .. import telemetry
from ..base import FatalError, MXNetError, TransientError
from ..resilience import chaos
from ..resilience.retry import classify, TRANSIENT
from ..telemetry import get_registry
from .admission import (AdmissionQueue, DeadlineExceeded, Request,
                        RequestCancelled, ServerOverload)
from .kv_cache import KVCache

__all__ = ["LLMEngine", "GenRequest"]

# what an LLMEngine built without the argument gets (num_blocks: enough
# for every lane at max_context; prefix_cache, kv_spill, kv_spill_serve:
# off)
DEFAULT_MAX_RUNNING = 8
DEFAULT_BLOCK_SIZE = 16
DEFAULT_DRAFT_K = 4


class GenRequest(Request):
    """One in-flight generation request.

    ``wait()`` returns the generated tokens as an int32 numpy array
    (length <= ``max_new_tokens``; generation stops after the first
    ``eos_token``, which is included). ``on_token`` (optional) streams
    each token from the scheduler thread as it is decoded — it must be
    cheap and must not raise (a raising callback fails the request).
    """

    __slots__ = ("prompt", "max_new_tokens", "eos_token", "on_token",
                 "tokens", "admitted_s", "prefill_s", "first_token_s",
                 "trace_id")

    def __init__(self, prompt, max_new_tokens: int, eos_token: int,
                 deadline: Optional[float],
                 on_token: Optional[Callable[[int], None]] = None,
                 trace_id: Optional[str] = None):
        super().__init__(prompt, 1, ("llm",), deadline)
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token = int(eos_token)
        self.on_token = on_token
        self.tokens: List[int] = []
        # when the scheduler took the request out of the queue into a
        # lane, on enqueue_t's clock (time.monotonic): the queue wait is
        # admitted_s - enqueue_t, and prefill is not in it
        self.admitted_s: Optional[float] = None
        self.prefill_s: Optional[float] = None
        self.first_token_s: Optional[float] = None
        # distributed-trace identity: minted at the cluster's front
        # door (Router admission) and propagated — the scheduler stamps
        # it into the step[llm_*] spans of every step that served this
        # request, so the merged cluster timeline is filterable per
        # request
        self.trace_id = trace_id


def _typed(exc: BaseException, what: str) -> BaseException:
    """``exc`` as the resilience classifier types it: itself where it
    already is a Transient- or FatalError, else the one of the two that
    :func:`classify` says, naming ``what`` faulted, with ``exc`` as its
    cause."""
    if isinstance(exc, (TransientError, FatalError)):
        return exc
    cls = TransientError if classify(exc) == TRANSIENT else FatalError
    typed = cls(f"{what}: {exc!r}")
    typed.__cause__ = exc
    return typed


class _Lane:
    """One decode lane: the request it carries + its block reservation."""

    __slots__ = ("req", "blocks", "pos", "last_token")

    def __init__(self, req: GenRequest, blocks: List[int], pos: int,
                 last_token: int):
        self.req = req
        self.blocks = blocks        # pool block ids owned by this lane
        self.pos = pos              # absolute position of the NEXT write
        self.last_token = last_token


# the prefill programs' manifest labels, by (suffix, draft)
_PREFILL_LABELS = {(False, False): "llm.prefill",
                   (False, True): "llm.draft_prefill",
                   (True, False): "llm.prefill_suffix",
                   (True, True): "llm.draft_suffix"}


@dataclasses.dataclass(eq=False)
class _Program:
    """One compiled serving program as the scheduler calls it.
    ``prog(*host)`` is ``run(params, *host[:pool_at], *pools,
    *host[pool_at:], key)`` with the cache manager's pools ``pair``
    (0 the target's, 1 the draft's; two or however many the model has):
    the pools it returns go back to the manager, the rest comes back as a
    list. The host arrays go to the device in one put first
    (:meth:`LLMEngine._upload`); the call that follows is the dispatch.
    Its first call records its
    warm-up manifest entry (``label``, ``bucket``); warm-up and the
    serving path call the same object, so a program's argument order is
    written at its call and nowhere else, and warm-up compiles for the
    arguments the serving path passes."""

    engine: "LLMEngine"
    run: Callable
    params: Dict
    pair: int
    pool_at: int
    label: str
    bucket: int
    fresh: bool = True          # never called: neither warm nor recorded

    def __call__(self, *host):
        eng, at = self.engine, self.pool_at
        pools = eng._kv.pools[self.pair]
        host = eng._upload(host)
        got = self.run(
            self.params, *host[:at], *pools, *host[at:], eng._next_key())
        out, pools[:] = list(got[:-len(pools)]), got[-len(pools):]
        if self.fresh:
            self.fresh = False
            # with the arguments as they are after the call (the pools
            # it returned): what resolved_key looks the signature up by
            eng._record_manifest(self, (self.params, *host[:at], *pools,
                                        *host[at:], eng._key))
        return out


class LLMMetrics:
    """Registry-backed metrics for one :class:`LLMEngine` (labelled
    ``engine=`` so several engines expose side by side; everything here
    lands in the flight-recorder snapshot automatically)."""

    _EVENTS = ("submitted", "admitted", "completed", "failed",
               "shed_overload", "shed_deadline", "retired_deadline",
               "cancelled", "prefills", "prefill_chunks",
               "decode_steps", "spec_steps", "resets", "compiles")

    def __init__(self, engine_id: str):
        reg = get_registry()
        self.engine_id = engine_id
        eng = {"engine": engine_id}
        self._events = reg.counter(
            "llm_events_total", "LLM serving lifecycle events",
            ("engine", "event"))
        self._counters = {e: self._events.labels(engine=engine_id, event=e)
                         for e in self._EVENTS}
        self._tokens = reg.counter(
            "llm_tokens_total", "Generated tokens", ("engine", "phase"))
        self.tokens_prefill = self._tokens.labels(engine=engine_id,
                                                  phase="prefill")
        self.tokens_decode = self._tokens.labels(engine=engine_id,
                                                 phase="decode")
        self.lanes_active = reg.gauge(
            "llm_lanes_active", "Decode lanes currently generating",
            ("engine",)).labels(**eng)
        self.lanes_total = reg.gauge(
            "llm_lanes_total", "Configured decode lanes (max_running)",
            ("engine",)).labels(**eng)
        self.pool_free = reg.gauge(
            "llm_pool_blocks_free", "KV pool blocks on the free list",
            ("engine",)).labels(**eng)
        self.pool_total = reg.gauge(
            "llm_pool_blocks_total", "KV pool blocks (allocatable)",
            ("engine",)).labels(**eng)
        self.tok_s = reg.gauge(
            "llm_tok_s", "Aggregate decode tokens/s (rolling)",
            ("engine",)).labels(**eng)
        self.step_ms = reg.histogram(
            "llm_step_ms", "Wall ms per scheduler step",
            ("engine", "phase"))
        self.decode_ms = self.step_ms.labels(engine=engine_id,
                                             phase="decode")
        self.prefill_ms = self.step_ms.labels(engine=engine_id,
                                              phase="prefill")
        self.spec_ms = self.step_ms.labels(engine=engine_id,
                                           phase="draft_verify")
        # speculative decoding: proposed vs accepted draft tokens (the
        # acceptance-rate numerator/denominator, cumulative) + the gauge
        self._spec_tokens = reg.counter(
            "llm_spec_tokens_total",
            "Speculative-decode draft tokens", ("engine", "result"))
        self.spec_proposed = self._spec_tokens.labels(engine=engine_id,
                                                      result="proposed")
        self.spec_accepted = self._spec_tokens.labels(engine=engine_id,
                                                      result="accepted")
        self.draft_acceptance_rate = reg.gauge(
            "llm_draft_acceptance_rate",
            "Cumulative accepted/proposed draft-token ratio",
            ("engine",)).labels(**eng)
        # prefix cache: prompt tokens served from resident blocks vs
        # prefilled, + the cumulative hit-rate gauge
        self._prefix_tokens = reg.counter(
            "llm_prefix_tokens_total",
            "Prompt tokens by prefix-cache outcome", ("engine", "result"))
        self.prefix_hit_tokens = self._prefix_tokens.labels(
            engine=engine_id, result="hit")
        self.prefix_miss_tokens = self._prefix_tokens.labels(
            engine=engine_id, result="miss")
        self.prefix_hit_rate = reg.gauge(
            "llm_prefix_hit_rate",
            "Cumulative prefix-cache hit ratio over prompt tokens",
            ("engine",)).labels(**eng)
        self.prefix_cached_blocks = reg.gauge(
            "llm_prefix_cached_blocks",
            "Pool blocks resident in the prefix cache",
            ("engine",)).labels(**eng)
        # tiered KV spill: eviction no longer means re-prefill — count
        # what left HBM, what is parked in the host tier, and what came
        # back by DMA instead of compute (per source tier)
        self.prefix_evictions = reg.counter(
            "llm_prefix_evictions_total",
            "Prefix-cache blocks evicted from the HBM pool (spilled "
            "when the spill tier is armed, dropped otherwise)",
            ("engine",)).labels(**eng)
        self.kv_spill_blocks = reg.gauge(
            "llm_kv_spill_blocks",
            "KV blocks resident in the host-RAM spill tier",
            ("engine",)).labels(**eng)
        self.kv_spill_bytes = reg.gauge(
            "llm_kv_spill_bytes",
            "Bytes held by the host-RAM spill tier",
            ("engine",)).labels(**eng)
        self._kv_reattach = reg.counter(
            "llm_kv_reattach_total",
            "Spilled KV blocks re-attached into the pool by source tier",
            ("engine", "tier"))
        # GSPMD sharding: mesh width + per-device KV footprint (the
        # largest-servable-model evidence: a pool whose TOTAL exceeds
        # one chip serves when the per-device share fits)
        self.shard_devices = reg.gauge(
            "llm_shard_devices",
            "Devices in the serving mesh (1 = unsharded)",
            ("engine",)).labels(**eng)
        self.shard_pool_bytes = reg.gauge(
            "llm_shard_pool_bytes_per_device",
            "KV pool bytes resident per device (head-sharded over tp)",
            ("engine",)).labels(**eng)
        # disaggregated serving: blocks a prefill-role engine exported
        # into its serving spill tier for the prefill->decode handoff
        self.handoff_exported = reg.counter(
            "llm_handoff_exported_blocks_total",
            "KV blocks exported by a prefill-role engine for handoff",
            ("engine",)).labels(**eng)
        # rows of keys and values the cache holds for live requests, by
        # family: every position in a full layer's blocks, the last
        # ``window`` in a window layer's ring (CacheGeometry.row_layers)
        self._kv_rows = reg.gauge(
            "llm_kv_rows", "K/V rows held for live requests, by the kind "
            "of layer that holds them", ("engine", "family"))
        self.kv_rows_full = self._kv_rows.labels(engine=engine_id,
                                                 family="full")
        self.kv_rows_window = self._kv_rows.labels(engine=engine_id,
                                                   family="window")
        self.queue_depth = reg.histogram(
            "llm_queue_depth", "Queue depth at admission",
            ("engine",)).labels(**eng)
        self.queue_wait_ms = reg.histogram(
            "llm_queue_wait_ms",
            "Submission to admission into a lane (ms), prefill not in it",
            ("engine",)).labels(**eng)
        # a mixture of experts told which experts it holds: per program
        # call, the fullest held expert's load over the mean load
        self.expert_load_ratio = reg.histogram(
            "llm_expert_load_max_over_mean",
            "Largest over mean load of a held expert, per program call",
            ("engine",)).labels(**eng)

    def observe_spec(self, proposed: int, accepted: int) -> None:
        self.spec_proposed.inc(proposed)
        self.spec_accepted.inc(accepted)
        tot = float(self.spec_proposed.value)
        if tot > 0:
            self.draft_acceptance_rate.set(
                float(self.spec_accepted.value) / tot)

    def count_reattach(self, tier: str, n: int = 1) -> None:
        self._kv_reattach.labels(engine=self.engine_id, tier=tier).inc(n)

    def observe_prefix(self, hit: int, miss: int) -> None:
        self.prefix_hit_tokens.inc(hit)
        self.prefix_miss_tokens.inc(miss)
        tot = (float(self.prefix_hit_tokens.value)
               + float(self.prefix_miss_tokens.value))
        if tot > 0:
            self.prefix_hit_rate.set(
                float(self.prefix_hit_tokens.value) / tot)

    # AdmissionQueue calls these two (the ServingMetrics seam)
    def count(self, name: str, delta: int = 1) -> None:
        c = self._counters.get(name)
        if c is None:
            c = self._events.labels(engine=self.engine_id, event=name)
            self._counters[name] = c
        c.inc(delta)

    def observe_queue_depth(self, depth: int) -> None:
        self.queue_depth.observe(float(depth))

    def counters(self) -> Dict[str, int]:
        return {name: int(c.value) for name, c in self._counters.items()}


_engine_seq = __import__("itertools").count()


class LLMEngine:
    """Continuous-batching generation over a paged KV block pool.

    Parameters
    ----------
    model : causal LM with the paged decode contract
        ``decode_step_paged`` / ``init_block_pool`` / ``cache_geometry``
        (+ the dense ``decode_step`` / ``init_cache`` used by whole-prompt
        prefill, or ``prefill_chunk_step`` where the geometry names a
        chunk) — :class:`~mxnet_tpu.gluon.model_zoo.bert._CausalLM`
        (blocks of K/V rows) and
        :class:`~mxnet_tpu.gluon.model_zoo.brumby._RetentionLM` (one
        state per request) provide them; ``docs/llm_serving.md``, "Two
        cache geometries", says which features each carries.
    max_running : int
        Decode lanes (the fixed batch axis of the ONE decode program).
        Default 8.
    block_size : int
        Positions per KV block. Default 16.
    max_context : int
        Longest prompt+generation a lane may hold. Defaults to the
        model's context window (``pos_embed`` rows), capped at 2048.
    num_blocks : int
        Pool capacity in blocks (+1 trash block is added internally).
        Default: enough for every lane at ``max_context`` (no
        admission ever waits on blocks).
        Smaller pools admit lazily: a request is admitted only when its
        worst-case ``ceil((prompt+max_new)/block_size)`` reservation
        fits the free list, so an in-flight sequence can never hit pool
        exhaustion mid-decode.
    kv_cache_dtype : str
        ``"int8"`` (default — the HBM-bound decode path reads half the
        bytes of bf16), or ``"float32"/"bfloat16"/"float16"`` for exact
        parity with the dense cache.
    weight_dtype : None | "int8"
        Weight-only int8 for the decode program (halves weight bytes
        per token; see :func:`generation.generate`).
    greedy / temperature / top_k / seed
        Sampling policy (engine-wide: it is baked into the compiled
        programs).
    max_queue_size / timeout_ms
        Admission bound and default deadline (admission -> prefill
        start), exactly the :class:`.admission.AdmissionQueue` contract.
    donate : bool, optional
        Donate the pool buffers to the decode/prefill programs (in-place
        pool update). Default: on for accelerator backends, off on CPU.
    draft_model : causal LM, optional
        Arms **speculative decoding**: a (small) draft model with the
        same paged contract proposes ``draft_k`` tokens per step; the
        target model verifies all of them in ONE batched (R, K+1)
        forward with exact rejection sampling — greedy output stays
        token-identical, sampled output distribution-exact. The draft
        runs its own block pools addressed by the SAME block tables, so
        admission/free/prefix-sharing govern both caches at once.
    draft_k : int, optional
        Draft tokens proposed per verify step. Default 4. The
        engine reserves ``draft_k``
        extra positions of block capacity per lane (verify writes up to
        K positions past the accepted length; rollback is just not
        advancing the position).
    prefix_cache : bool, optional
        Arms **shared-prefix block caching**: full prompt blocks are
        chain-hashed at admission (:mod:`.kv_hash` — the same
        discipline the fleet router's prefix-affinity dispatch keys
        on); a request whose leading blocks are resident reuses them
        copy-on-write (per-block refcounts; a block is freed only at
        refcount zero) and prefills ONLY its uncached suffix. Default
        off.
    kv_spill : bool, optional
        Arms **tiered KV block storage** (requires ``prefix_cache``):
        a refcount-0 LRU block evicted from the pool spills its exact
        rows to a bounded host-RAM tier
        (:class:`~mxnet_tpu.serving.kv_spill.KVSpillTier`) instead of
        being dropped — optionally demoting to a content-addressed
        disk tier (``kv_spill_dir``) — and a later admission whose
        prefix misses HBM but hits a spill tier re-attaches by DMA
        instead of re-prefilling (token-identical: the payload is the
        raw pool rows). Default off.
    kv_spill_bytes / kv_spill_dir / kv_spill_serve / kv_spill_peers :
        Spill-tier shape: host-RAM byte bound
        (``MXNET_TPU_LLM_KV_SPILL_BYTES``, 256 MiB), disk tier root
        (``MXNET_TPU_LLM_KV_SPILL_DIR``), expose spilled blocks to
        remote replicas over a
        :class:`~mxnet_tpu.io.transport.BlockServer` (default off;
        endpoint at :attr:`kv_spill_endpoint`), and peer endpoints to
        fetch from
        (``MXNET_TPU_LLM_KV_SPILL_PEERS``) — a session resuming on a
        *different* replica re-attaches over the transport plane.
    step_hook : callable, optional
        Called at the top of every scheduler tick, inside the fault
        containment (an exception it raises is typed through the
        resilience classifier exactly like a program fault). The fleet
        layer (:mod:`.fleet`) uses it as the per-replica chaos
        injection point; anything it does must be cheap.
    mesh : jax.sharding.Mesh, optional
        Arms **GSPMD-sharded serving**: params are partitioned by
        ``rules`` (default the
        :data:`~mxnet_tpu.parallel.sharding.TRANSFORMER_RULES`
        megatron tp column/row catalog), the KV block pools become
        global arrays sharded on the heads of every row
        (``P(None, None, None, "tp")`` — ``tp`` must divide the heads),
        and every paged program runs as a global-array program over the
        mesh by input-sharding propagation. Token-identical to the
        unsharded engine; donation and the ``_decode_cache``/AOT
        fingerprint discipline are preserved (the mesh topology already
        folds into both). This is how a model whose KV/param bytes
        exceed one chip serves: per-device share = total / tp.
    rules : list of (regex, PartitionSpec), optional
        Partition-rule tree for ``mesh=`` (see above).
    role : None | "prefill" | "decode"
        Arms **disaggregated serving** (:mod:`.disagg`). A
        ``"prefill"`` engine exports every freshly prefilled full
        block's exact rows into its (serving) spill tier, keyed by the
        shared chain hashes; a ``"decode"`` engine probes the prefill
        fleet's export endpoints (wired via
        :meth:`set_kv_spill_peers`) as its remote spill tier, so
        admission re-attaches shipped blocks by DMA and decodes
        without re-prefilling. Both roles force ``prefix_cache`` +
        ``kv_spill`` on.

    Notes
    -----
    A request's ``timeout_ms`` deadline is an **end-to-end budget**:
    admission wait + queue + prefill + decode. A lane whose deadline
    passes mid-decode is retired at the next scheduler tick — blocks
    freed, request failed :class:`~.admission.DeadlineExceeded`
    carrying ``elapsed_s`` vs ``budget_s`` — instead of streaming
    tokens to a client that already gave up. ``GenRequest.cancel()``
    retires a lane the same way (:class:`~.admission.RequestCancelled`)
    — the fleet router's first-wins hedge cancellation.
    """

    def __init__(self, model, *, max_running: Optional[int] = None,
                 block_size: Optional[int] = None,
                 max_context: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 kv_cache_dtype: Optional[str] = "int8",
                 weight_dtype: Optional[str] = None,
                 greedy: bool = True, temperature: float = 1.0,
                 top_k: int = 0, seed: int = 0,
                 eos_token: int = -1,
                 max_queue_size: int = 256,
                 timeout_ms: Optional[float] = None,
                 donate: Optional[bool] = None,
                 draft_model=None, draft_k: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_spill: Optional[bool] = None,
                 kv_spill_bytes: Optional[int] = None,
                 kv_spill_dir: Optional[str] = None,
                 kv_spill_serve: Optional[bool] = None,
                 kv_spill_peers: Optional[List[str]] = None,
                 step_hook: Optional[Callable[[], None]] = None,
                 metrics: Optional[LLMMetrics] = None,
                 mesh=None, rules=None, role: Optional[str] = None):
        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"role {role!r} not supported (None/'prefill'/'decode')")
        self.role = role
        self._mesh = mesh
        if mesh is not None:
            if weight_dtype is not None:
                raise MXNetError(
                    "mesh= with weight_dtype is not supported: the "
                    "int8-weight wrapper re-keys the param tree out from "
                    "under the partition rules")
            from ..parallel.sharding import TRANSFORMER_RULES

            self._rules = list(rules) if rules is not None \
                else list(TRANSFORMER_RULES)
        else:
            self._rules = None
        # where a program's host arrays are put: replicated over the
        # mesh, or the default device
        self._upload_to = jax.devices()[0] if mesh is None else \
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

        if max_running is None:
            max_running = DEFAULT_MAX_RUNNING
        if block_size is None:
            block_size = DEFAULT_BLOCK_SIZE
        if max_running < 1 or block_size < 1:
            raise ValueError("max_running and block_size must be >= 1")
        self.max_running = int(max_running)
        self.block_size = int(block_size)
        # the model states the cache's geometry, the cache manager
        # allocates: what a pool block is (block_size K/V rows, or one
        # request's whole state), what a request of n tokens reserves,
        # and which features the cache cannot carry
        # (generation.CacheGeometry). This is the one place it is asked
        # for; everything below is one path.
        geom = self._geom = model.cache_geometry(self.block_size)
        model_ctx = geom.max_positions
        if max_context is None:
            max_context = min(model_ctx or 2048, 2048)
        if model_ctx is not None and max_context > model_ctx:
            raise MXNetError(
                f"max_context {max_context} exceeds the model's context "
                f"window ({model_ctx} positions)")
        self.max_context = int(max_context)
        # the width of a lane's block table: 1 where a block is a state,
        # so there max_context bounds positions and nothing else
        self.max_blocks_per_seq = geom.blocks_for(self.max_context)
        if num_blocks is None:
            num_blocks = self.max_running * self.max_blocks_per_seq
        if num_blocks < 1:
            raise ValueError("num_blocks must be >= 1")
        self.num_blocks = int(num_blocks)
        self._weight_dtype = weight_dtype
        self._greedy = bool(greedy)
        self._temperature = float(temperature)
        self._top_k = int(top_k)
        self._eos = int(eos_token)
        self._timeout_ms = timeout_ms
        self._model = model
        self._key = jax.random.PRNGKey(seed)
        self._step_seq = 0

        # speculative decoding (armed by a draft model)
        self._draft = draft_model
        if draft_k is None:
            draft_k = DEFAULT_DRAFT_K
        self._draft_k = max(int(draft_k), 1)
        self._spec = draft_model is not None
        # verify writes up to draft_k positions past the accepted
        # length; the block reservation carries that slack
        self._slack = self._draft_k if self._spec else 0

        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)

        self.metrics = metrics or LLMMetrics(str(next(_engine_seq)))
        self.metrics.lanes_total.set(self.max_running)
        self.metrics.pool_total.set(self.num_blocks)

        # the pools and everything that indexes them: free list,
        # refcounts, the shared-prefix index (off unless armed: callers
        # that pin "free list returns to full" keep that invariant) and
        # the spill tiers under it. The draft model's pools are addressed
        # by the SAME block tables/ids as the target's (one allocation
        # governs both).
        self._kv = KVCache(
            model, geom, num_blocks=self.num_blocks,
            block_size=self.block_size, kv_cache_dtype=kv_cache_dtype,
            metrics=self.metrics, max_running=self.max_running,
            draft_model=draft_model,
            prefix_cache=prefix_cache, kv_spill=kv_spill,
            kv_spill_bytes=kv_spill_bytes, kv_spill_dir=kv_spill_dir,
            kv_spill_serve=kv_spill_serve, kv_spill_peers=kv_spill_peers,
            role=role, mesh=mesh)

        # lane state (host side; device arrays mirror it each step)
        self._lanes: List[Optional[_Lane]] = [None] * self.max_running
        self._bt = onp.full((self.max_running, self.max_blocks_per_seq),
                            self._kv.trash, onp.int32)
        self._pos = onp.zeros((self.max_running,), onp.int32)
        self._toks = onp.zeros((self.max_running, 1), onp.int32)
        # the token at positions-1 per lane (the draft catch-up input)
        self._prev = onp.zeros((self.max_running, 1), onp.int32)

        # compiled programs (memoized per model config in generation.py;
        # compiled through aot.cached_jit, so MXNET_TPU_AOT_CACHE serves
        # fresh replicas with zero cold compiles), each behind the one
        # helper the serving path and warm-up both call
        from .. import aot
        from ..gluon.model_zoo.generation import (
            paged_decode_program, paged_spec_draft_program,
            paged_spec_verify_program, state_prefill_program)

        self._warmup_manifest = aot.WarmupManifest()
        self._paged = dict(block_size=self.block_size,
                           kv_cache_dtype=self._kv.dtype)
        step = dict(self._paged, max_running=self.max_running,
                    max_blocks_per_seq=self.max_blocks_per_seq)
        # GSPMD serving: committed NamedSharding params/pools make the
        # existing plain-jit programs global-array programs — sharding
        # propagates from the inputs, no per-program in_shardings
        self._params: Dict[bool, Dict] = {}     # by "the draft's?"
        self._decode = self._program(
            paged_decode_program, "llm.decode", self.max_running, 1,
            weight_dtype=weight_dtype, **step)
        # prefill: whole prompts in length buckets (built on demand,
        # _prefill_program), or (a state) chunks of one fixed size that
        # carry it — one program, one warm-up shape
        self._chunk = geom.prefill_chunk
        if self._chunk:
            # (a state beside blocks: the chunk also writes its rows
            # through the lane's table, so the program is told the blocks)
            self._chunk_step = self._program(
                state_prefill_program, "llm.prefill_chunk", self._chunk, 3,
                chunk=self._chunk, **(dict(
                    self._paged, max_blocks_per_seq=self.max_blocks_per_seq)
                    if geom.lane_state else {}))
        self._bucketed: Dict[tuple, _Program] = {}
        if self._spec:
            self._draft_step = self._program(
                paged_spec_draft_program, "llm.draft", self._draft_k, 2,
                draft=True, draft_k=self._draft_k, weight_dtype=None, **step)
            self._verify = self._program(
                paged_spec_verify_program, "llm.verify", self._draft_k, 3,
                draft_k=self._draft_k, weight_dtype=weight_dtype, **step)
        self.metrics.shard_devices.set(
            int(mesh.devices.size) if mesh is not None else 1)
        self.metrics.shard_pool_bytes.set(self._kv.bytes_per_device())

        # scheduler; the state lock covers pool/lane mutation (the
        # scheduler tick vs a caller-thread warmup())
        self._state_lock = threading.RLock()
        self._step_hook = step_hook
        # scheduler-loop liveness: monotonic stamp of the last completed
        # tick. A wedged scheduler (stuck inside a step) stops advancing
        # it, which is what the fleet health monitor keys "wedged" off.
        self.last_tick = time.monotonic()
        self._queue = AdmissionQueue(max_queue_size, self.metrics)
        self._closed = False
        self._drain = True
        self._broken: Optional[BaseException] = None
        self._close_lock = threading.Lock()
        self._tok_window: List = []     # (t, n) for the rolling tok/s gauge
        self._thread = threading.Thread(target=self._loop,
                                        name="llm-scheduler", daemon=True)
        self._thread.start()
        # /healthz answers from the SAME seam the fleet heartbeats gate
        # on: an external probe sees a wedged scheduler exactly when
        # the in-cluster health monitor does (unregistered at close)
        from ..telemetry import exporter as _texporter

        _texporter.register_liveness(
            f"llm:{self.metrics.engine_id}",
            lambda: {"alive": self.alive, "last_tick": self.last_tick})

    # -- GSPMD sharding (mesh=) --------------------------------------------
    def _mesh_ctx(self):
        """The mesh scope every device-dispatch seam runs under. The
        mesh stack is thread-local, so the scheduler thread must enter
        it itself; entering it is also what folds the topology into the
        AOT dispatch signature / persistent fingerprint
        (``aot.cache._mesh_sig`` / ``_mesh_component``) — the
        ``_decode_cache`` discipline needs no per-mesh cache keys."""
        if self._mesh is None:
            import contextlib

            return contextlib.nullcontext()
        from ..parallel.mesh import use_mesh

        return use_mesh(self._mesh)

    def _shard_params(self, params):
        """Partition the flat param dict by the rule catalog
        (megatron tp column/row via ``TRANSFORMER_RULES`` unless the
        caller brought its own tree) and commit it to the mesh. With
        committed inputs, GSPMD propagates the layout through the
        plain-jit paged programs — decode/prefill/suffix/spec all
        become global-array programs without per-program shardings."""
        if self._mesh is None:
            return params
        from ..parallel.sharding import match_partition_rules, shard_tree

        specs = match_partition_rules(self._rules, params)
        return shard_tree(params, specs, self._mesh)

    # -- prompt bucketing --------------------------------------------------
    def _prefill_bucket(self, p: int) -> int:
        """Smallest pow2 multiple of block_size >= p, capped at the
        block-covered context (one compiled prefill program per bucket
        — the engine's pow2 ladder policy applied to the block axis)."""
        from .engine import _pow2_bucket

        return self.block_size * _pow2_bucket(
            -(-p // self.block_size), self.max_blocks_per_seq)

    # -- the compiled programs ---------------------------------------------
    def _program(self, build, label: str, bucket: int, pool_at: int,
                 draft: bool = False, **shape) -> "_Program":
        """One of generation.py's programs (``build``) for the target
        model or the draft, behind the call helper. A model's programs
        share one param tree: that of the first built (the decode
        program's, the draft program's), laid out on the mesh."""
        run, params = build(
            self._draft if draft else self._model,
            num_blocks=self.num_blocks + 1, greedy=self._greedy,
            temperature=self._temperature, top_k=self._top_k,
            donate=self._donate, **shape)
        if draft not in self._params:
            self._params[draft] = self._shard_params(params)
        return _Program(self, run, self._params[draft], int(draft), pool_at,
                        label, bucket)

    def _prefill_program(self, bucket: int, suffix: bool = False,
                         draft: bool = False) -> "_Program":
        """The whole-prompt prefill program of one length bucket, or the
        ``suffix`` one that runs behind cached blocks; for the target or
        the ``draft`` model. Built at its first use."""
        label = _PREFILL_LABELS[suffix, draft]
        prog = self._bucketed.get((label, bucket))
        if prog is None:
            from ..gluon.model_zoo.generation import (
                paged_prefill_program, paged_suffix_prefill_program)

            shape = dict(self._paged,
                         weight_dtype=None if draft else self._weight_dtype)
            if suffix:
                build, pool_at = paged_suffix_prefill_program, 3
                shape.update(suffix_len=bucket,
                             max_blocks_per_seq=self.max_blocks_per_seq)
            else:
                build, pool_at = paged_prefill_program, 2
                shape.update(prefill_len=bucket)
            prog = self._bucketed[label, bucket] = self._program(
                build, label, bucket, pool_at, draft=draft, **shape)
        return prog

    def _upload(self, host) -> list:
        """``host`` with its numpy arrays on the program's devices, inside
        the span ``llm.upload`` (``bytes``: their size). A device array (a
        draft program's proposals) stays as it is. Every program call
        passes here (``_Program``).

        Over a mesh the arrays are replicated by one ``jax.device_put``.
        On one device the device's client puts each, as the jit does with
        a numpy argument (which it may alias until the transfer is done:
        the scheduler rewrites its lane arrays only once the call's
        result is back). On a TPU v5e's host a trivial program called on
        a decode step's three arrays and waited for took 0.86-0.89 ms
        with them passed as numpy, 0.88-0.92 put this way and 1.17-1.19
        put by ``jax.device_put``."""
        at = [i for i, a in enumerate(host)
              if isinstance(a, (onp.ndarray, onp.generic))]
        arrays = [host[i] for i in at]
        out = list(host)
        with telemetry.span("llm.upload", args={
                "bytes": sum(a.nbytes for a in arrays)}):
            if self._mesh is not None:
                put = jax.device_put(arrays, self._upload_to)
            else:
                dev = self._upload_to
                put = [dev.client.buffer_from_pyval(a, dev) for a in arrays]
            for i, a in zip(at, put):
                out[i] = a
        return out

    @staticmethod
    def _result(*got) -> list:
        """What the scheduler reads of a program's outputs ``got``: the
        host waits for the program and copies each back with one
        ``onp.asarray``, inside the span ``llm.result``. Every program
        output that reaches the host passes here."""
        with telemetry.span("llm.result"):
            return [onp.asarray(a) for a in got]

    # -- the cache manager's surface, as the fleet layer reads it ----------
    def evictable_blocks(self) -> int:
        """Prefix-cache residents nothing else references — blocks the
        cache manager reclaims on demand (:meth:`KVCache.evictable`: an
        advisory read, no scheduler lock)."""
        return self._kv.evictable()

    @property
    def kv_spill_endpoint(self) -> Optional[str]:
        """``host:port`` of this engine's spill BlockServer (None
        unless ``kv_spill_serve`` armed it) — what a peer engine puts
        in its ``kv_spill_peers`` list."""
        return self._kv.endpoint

    def set_kv_spill_peers(self, peers: List[str]) -> None:
        """(Re)wire the spill tier's remote peers. The disagg router
        points every decode-role engine at the live prefill fleet's
        export endpoints through this, re-calling it on each scale or
        death event; a no-spill engine ignores it."""
        self._kv.set_peers(peers)

    # -- client surface ----------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int,
               eos_token: Optional[int] = None,
               timeout_ms="default",
               on_token: Optional[Callable[[int], None]] = None,
               trace_id: Optional[str] = None) -> GenRequest:
        """Enqueue one prompt (1-D int sequence). Returns the
        :class:`GenRequest` handle; ``handle.wait()`` yields the
        generated int32 tokens. Raises :class:`ServerOverload` when the
        admission queue is full."""
        if self._closed:
            raise ServerOverload("LLM engine is closed")
        if self._broken is not None:
            raise ServerOverload(
                f"LLM engine stopped on a fatal fault: {self._broken!r}")
        prompt = onp.asarray(prompt_ids, onp.int32).reshape(-1)
        p = int(prompt.shape[0])
        if p < 1:
            raise ValueError("prompt must have >= 1 token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        slack_note = (f" (+ draft_k {self._slack} speculative slack)"
                      if self._slack else "")
        if p + max_new_tokens + self._slack > self.max_context:
            raise ValueError(
                f"prompt {p} + max_new_tokens {max_new_tokens}"
                f"{slack_note} exceeds max_context {self.max_context}")
        if self._geom.blocks_for(p + max_new_tokens + self._slack) \
                > self.num_blocks:
            raise ValueError(
                f"request needs more KV blocks than the whole pool holds "
                f"({self.num_blocks} x {self.block_size}){slack_note} — "
                "it could never be admitted")
        if timeout_ms == "default":
            timeout_ms = self._timeout_ms
        deadline = (time.monotonic() + timeout_ms / 1e3
                    if timeout_ms is not None else None)
        if trace_id is None:
            ctx = telemetry.current_trace()
            trace_id = ctx.trace_id if ctx is not None else None
        req = GenRequest(prompt, max_new_tokens,
                         self._eos if eos_token is None else eos_token,
                         deadline, on_token, trace_id=trace_id)
        self._queue.submit(req)         # may raise ServerOverload
        self.metrics.count("submitted")
        return req

    def generate(self, prompt_ids, max_new_tokens: int, **kw):
        """Blocking convenience: submit + wait."""
        return self.submit(prompt_ids, max_new_tokens, **kw).wait()

    def snapshot_cache(self, req: GenRequest):
        """What the cache holds for an in-flight request, taken between
        two ticks: ``(positions, tokens, k, v)`` — how many positions the
        cache has absorbed (the prompt and every emitted token but the
        last, which the next step feeds), the tokens emitted so far, and
        the request's blocks of the two pools (``pool[:, blocks]``: its
        rows of keys and values, or a state cache's one slot; where a
        lane holds a state beside its blocks, the lane's slot of the two
        state pools) as new device arrays. ``None`` when no lane carries
        the request."""
        with self._state_lock, self._mesh_ctx():
            for i, lane in enumerate(self._lanes):
                if lane is not None and lane.req is req:
                    return (lane.pos, list(req.tokens),
                            *self._kv.snapshot(lane.blocks, i))
        return None

    # -- scheduler ---------------------------------------------------------
    def _loop(self) -> None:
        while True:
            try:
                idle = self._tick()
            except Exception as e:  # noqa: BLE001 — typed + contained
                self.last_tick = time.monotonic()
                if not self._fault(e):
                    return
                continue
            self.last_tick = time.monotonic()
            if idle is None:        # closed and drained
                return
            if idle:
                time.sleep(0.001)

    def _tick(self):
        """One scheduler iteration: admit into free lanes, then run one
        decode step. Returns True when there is nothing to do (caller
        sleeps a tick), None when closed-and-drained."""
        with self._state_lock, self._mesh_ctx():
            return self._tick_locked()

    def _observe_rows(self) -> dict:
        """The K/V rows the cache holds for the requests in the lanes, by
        family, where the model's layers are of two kinds (``{}`` where
        they are not): positions x full layers, and the last ``window``
        of them x window layers. The tick sets the two gauges here and
        nothing else does; ``stats()`` reads them back."""
        if self._geom.row_layers is None:
            return {}
        full, ring, window = self._geom.row_layers
        at = [ln.pos for ln in self._lanes if ln is not None]
        held = {"kv_rows_full": full * sum(at),
                "kv_rows_window": ring * sum(min(p, window) for p in at)}
        self.metrics.kv_rows_full.set(held["kv_rows_full"])
        self.metrics.kv_rows_window.set(held["kv_rows_window"])
        return held

    def _tick_locked(self):
        occupied = sum(1 for ln in self._lanes if ln is not None)
        with telemetry.span("llm.tick", args={
                "active": occupied, "queue_len": len(self._queue),
                "blocks_in_use": self._kv.blocks_in_use,
                **self._observe_rows()}) as tick:
            if self._step_hook is not None:
                # inside the containment: a hook fault (e.g. an armed
                # serving.fleet.replica chaos rule) routes through _fault
                self._step_hook()
            if occupied:
                self._sweep_lanes()
            active = [i for i in range(self.max_running)
                      if self._lanes[i] is not None]
            free = [i for i in range(self.max_running)
                    if self._lanes[i] is None]
            took = 0
            if free and (len(self._queue) or not active):
                got = self._queue.take(
                    max_items=len(free), max_wait_s=0.0,
                    poll_s=0.02 if not active else 1e-4)
                took = len(got)
                try:
                    while got:
                        self._admit(got.pop(0), free.pop(0))
                except Exception as e:
                    # an admission escalation (donated-buffer reset) aborts
                    # the tick: _admit already failed ITS request, but
                    # siblings popped from the queue in the same take() are
                    # in neither a lane nor the queue — fail them typed
                    # (transient: the client retry loop resubmits) instead
                    # of orphaning their wait() forever
                    for req in got:
                        req.fail(ServerOverload(
                            f"engine resetting mid-admission: {e!r}"))
                        self.metrics.count("failed")
                    raise
                active = [i for i in range(self.max_running)
                          if self._lanes[i] is not None]
            if not active:
                if not occupied and not took:
                    # no lane held a request and none came: an idle engine
                    # spins here a thousand times a second, and those ticks
                    # would push everything else out of the ring
                    tick.ring = False
                if self._closed and not len(self._queue):
                    return None
                return True
            if self._spec:
                self._spec_step(active)
            else:
                self._decode_step(active)
            return False

    def _sweep_lanes(self) -> None:
        """Retire lanes whose request no longer wants to run: cancelled
        (a submitter gave up, or a fleet hedge twin already won —
        first-wins cancellation) or past its end-to-end deadline budget
        mid-decode (the work would stream to a client that already gave
        up; retire it and free the blocks instead). Runs at the top of
        every tick, so a freed lane is admittable the same tick."""
        with telemetry.span("llm.sweep") as sp:
            now = time.monotonic()
            retired = 0
            for i in range(self.max_running):
                lane = self._lanes[i]
                if lane is None:
                    continue
                req = lane.req
                if req.cancelled:
                    retired += 1
                    self._release(lane, i)
                    if req.fail(RequestCancelled(
                            "request cancelled mid-generation — lane "
                            f"retired after {len(req.tokens)} token(s)")):
                        self.metrics.count("cancelled")
                    continue
                if req.deadline is not None and now > req.deadline:
                    elapsed = now - req.enqueue_t
                    budget = req.deadline - req.enqueue_t
                    retired += 1
                    self._release(lane, i)
                    if req.fail(DeadlineExceeded(
                            f"deadline passed mid-decode ({elapsed * 1e3:.1f} "
                            f"ms elapsed vs a {budget * 1e3:.1f} ms budget, "
                            f"{len(req.tokens)} token(s) generated) — lane "
                            "retired, remaining work not spent",
                            elapsed_s=elapsed, budget_s=budget)):
                        self.metrics.count("retired_deadline")
            sp.args["retired"] = retired
            if retired:
                self.metrics.lanes_active.set(
                    sum(1 for ln in self._lanes if ln is not None))

    def _admit(self, req: GenRequest, lane_idx: int) -> None:
        """Prefill ``req`` into ``lane_idx`` (or shed it typed: expired
        deadline, or a pool that cannot hold its worst-case block
        reservation — the conservative no-preemption policy documented
        in docs/llm_serving.md). With the prefix cache armed, resident
        leading full blocks are shared (refcounted, read-only) and only
        the uncached suffix prefills.

        Containment: a fault anywhere in admission must never orphan
        ``req`` — a request popped from the queue but failed by nobody
        hangs its client's ``wait()`` forever. Program faults are
        contained inside :meth:`_admit_locked` (fail THIS request, keep
        serving); anything escaping it (a pre-containment bookkeeping
        bug, or the donated-buffer escalation) fails the request typed
        here first-wins, then propagates to :meth:`_fault` so pool /
        cache / refcount state rebuilds consistently."""
        try:
            with telemetry.span(
                    "llm.admit", args={"trace_id": req.trace_id}
                    if req.trace_id is not None else None) as sp:
                self._admit_locked(req, lane_idx, sp)
        except Exception as e:  # noqa: BLE001 — typed + escalated
            # no-op when already failed inside
            if req.fail(_typed(e, "LLM admission fault")):
                self.metrics.count("failed")
            raise

    def _admit_locked(self, req: GenRequest, lane_idx: int, sp) -> None:
        now = time.monotonic()
        sp.args["queue_wait_ms"] = round((now - req.enqueue_t) * 1e3, 3)
        if req.expired(now):
            self.metrics.count("shed_deadline")
            req.fail(DeadlineExceeded(
                f"deadline passed while queued ({req.latency_s * 1e3:.1f} "
                "ms) — shed before prefill"))
            return
        p = int(req.prompt.shape[0])
        bs = self.block_size
        n_tokens = p + req.max_new_tokens + self._slack
        sp.args["prompt_tokens"] = p
        res = self._kv.reserve(req.prompt, n_tokens, self._suffix_fits)
        if res is None:
            # no free blocks: shed typed-transient so the client's retry
            # loop backs off and resubmits (never blocks the decode batch)
            self.metrics.count("shed_overload")
            req.fail(ServerOverload(
                f"KV pool exhausted ({self._kv.free_blocks} free blocks, "
                f"need {self._geom.blocks_for(n_tokens)}) — back off and "
                "retry"))
            return
        blocks, n_hit = res.blocks, res.n_hit
        # the request has its blocks: it is admitted, and has waited
        # from submission until the top of this call
        req.admitted_s = now
        self.metrics.queue_wait_ms.observe(sp.args["queue_wait_ms"])
        sp.args.update(
            bucket=self._chunk or self._prefill_bucket(p - n_hit * bs),
            blocks=len(blocks), prefix_hit_blocks=n_hit)
        ran = False
        try:
            # the chaos injection point for the splice path: an injected
            # fault fails THIS request (typed through the classifier),
            # injected latency holds the scheduler (deadline drills)
            chaos.site("serving.llm", phase="prefill_splice",
                       prefix_hit_blocks=n_hit)
            with telemetry.step("llm_prefill") as st:
                tid = None
                if req.trace_id is not None:
                    tid = {"trace_id": req.trace_id}
                    st.annotate("trace_id", req.trace_id)
                with st.phase("device", "llm.prefill", tid) as prefill:
                    ran = True
                    if n_hit:
                        first = self._suffix_prefill(req.prompt, blocks,
                                                     n_hit)
                    elif self._chunk:
                        first = self._chunk_prefill(req.prompt, blocks,
                                                    lane_idx)
                    else:
                        first = self._full_prefill(req.prompt, blocks)
        except Exception as e:
            # contained: the fault fails THIS request, typed through the
            # classifier; the engine keeps serving
            self._kv.release(blocks)
            req.fail(_typed(e, "LLM prefill fault"))
            self.metrics.count("failed")
            self.metrics.count("resets")
            if ran and self._donate:
                # the failed program call may have consumed the donated
                # pool buffers — escalate to the full reset path (the
                # request is already failed; lanes/pool rebuild there)
                raise
            return
        self.metrics.count("prefills")
        self.metrics.prefill_ms.observe(prefill.dur_s * 1e3)
        self.metrics.tokens_prefill.inc()
        self._kv.commit(res, p)
        req.prefill_s = prefill.dur_s
        req.first_token_s = req.latency_s
        lane = _Lane(req, blocks, pos=p, last_token=first)
        if not self._push_token(lane, first):
            self._release(lane, None)
            return
        if self._retire_if_done(lane, lane_idx=None):
            return
        self._lanes[lane_idx] = lane
        self._bt[lane_idx, :] = self._kv.trash
        self._bt[lane_idx, :len(blocks)] = blocks
        self._pos[lane_idx] = lane.pos
        self._toks[lane_idx, 0] = lane.last_token
        self._prev[lane_idx, 0] = int(req.prompt[-1])
        self.metrics.count("admitted")
        self.metrics.lanes_active.set(
            sum(1 for ln in self._lanes if ln is not None))

    def _suffix_fits(self, run: int, rest: int) -> bool:
        """Can the ``rest`` of a prompt be prefilled behind ``run`` cached
        blocks? Not where its bucket would reach past the block-covered
        context window: the prompt then prefills whole
        (``KVCache.reserve`` asks: the buckets are the scheduler's)."""
        return (run + self._prefill_bucket(rest) // self.block_size
                <= self.max_blocks_per_seq)

    def _full_prefill(self, prompt, blocks: List[int]) -> int:
        """Bucketed whole-prompt prefill (+ the draft model's, writing
        the SAME block ids into its own pools, when spec is armed).
        Warm-up calls it with a prompt that fills the bucket and no
        blocks: every row then lands in the trash block."""
        p = int(prompt.shape[0])
        bucket = self._prefill_bucket(p)
        real = blocks[:-(-p // self.block_size)]
        ids = onp.full((bucket // self.block_size,), self._kv.trash,
                       onp.int32)
        ids[:len(real)] = real
        padded = onp.zeros((1, bucket), onp.int32)
        padded[0, :p] = prompt
        first, = self._prefill_program(bucket)(padded, onp.int32(p - 1), ids)
        if self._spec:
            self._prefill_program(bucket, draft=True)(
                padded, onp.int32(p - 1), ids)
        first, = self._result(first)
        return int(first)

    def _chunk_where(self, blocks: List[int], lane_idx: int) -> tuple:
        """Where a chunk's program finds the lane's cache: the slot of
        its state — the block it reserved, or (a state beside blocks) the
        lane's own index, and then also its table of blocks."""
        if not self._geom.lane_state:
            return (onp.int32(blocks[0]),)
        table = onp.full((self.max_blocks_per_seq,), self._kv.trash,
                         onp.int32)
        table[:len(blocks)] = blocks
        return onp.int32(lane_idx), table

    def _chunk_prefill(self, prompt, blocks: List[int],
                       lane_idx: int) -> int:
        """Prefill a prompt of any length as a loop over the one chunk
        program, the lane's state carried from chunk to chunk in its
        slot (the first chunk starts it from zero inside the program).
        All of a prompt's chunks run in the tick that
        admits it, as a whole-prompt prefill does; each is waited for,
        so that ``llm.prefill.chunk`` is the chunk's time on the chip and
        not its launch."""
        p, c = int(prompt.shape[0]), self._chunk
        where = self._chunk_where(blocks, lane_idx)
        for start in range(0, p, c):
            n = min(c, p - start)
            padded = onp.zeros((1, c), onp.int32)
            padded[0, :n] = prompt[start:start + n]
            with telemetry.span("llm.prefill.chunk",
                                args={"tokens": n, "pad": c - n,
                                      "start": start}) as sp:
                got, = self._chunk_step(padded, onp.int32(start),
                                        onp.int32(n), *where)
                first = self._result(got)[0].reshape(-1)
                self._observe_experts(first[1:], sp.args)
            self.metrics.count("prefill_chunks")
        return int(first[0])

    def _observe_experts(self, counts, args: dict) -> None:
        """What a program's expert layers counted on the device, fetched
        with its token (``ops.experts``: assignments on held experts,
        held experts touched, the largest load of one, experts held; over
        the layers): into the span's args, the counters and the
        max-over-mean histogram. Nothing where the model has no such
        layer."""
        if not len(counts):
            return
        hit, touched, most, held = (int(v) for v in counts)
        args.update(moe_assignments=hit, moe_experts_touched=touched,
                    moe_max_load=most, moe_experts_held=held)
        self.metrics.count("moe_assignments", hit)
        self.metrics.count("moe_experts_touched", touched)
        if hit:
            self.metrics.expert_load_ratio.observe(most * held / hit)

    def _suffix_prefill(self, prompt, blocks: List[int], n_hit: int) -> int:
        """Prefill ONLY the uncached suffix: one multi-token paged step
        attending over the resident prefix blocks through the lane's
        table — the cached prefix's prefill compute is skipped
        entirely."""
        start = n_hit * self.block_size
        s = int(prompt.shape[0]) - start
        bucket = self._prefill_bucket(s)
        padded = onp.zeros((1, bucket), onp.int32)
        padded[0, :s] = prompt[start:]
        table = onp.full((1, self.max_blocks_per_seq), self._kv.trash,
                         onp.int32)
        table[0, :len(blocks)] = blocks
        args = (padded, onp.int32(start), onp.int32(s - 1), table)
        first, = self._prefill_program(bucket, suffix=True)(*args)
        if self._spec:
            self._prefill_program(bucket, suffix=True, draft=True)(*args)
        first, = self._result(first)
        return int(first)

    def _lane_trace_ids(self, active: List[int]) -> List[str]:
        """The distributed-trace ids of the requests the active lanes
        carry (annotated onto every decode/spec step span so the
        merged cluster timeline shows WHICH requests each step
        served)."""
        out: List[str] = []
        for i in active:
            lane = self._lanes[i]
            tid = getattr(lane.req, "trace_id", None) if lane else None
            if tid is not None:
                out.append(tid)
        return out

    def _decode_step(self, active: List[int]) -> None:
        self._step_seq += 1
        with telemetry.step("llm_decode", self._step_seq) as st:
            tids = self._lane_trace_ids(active)
            at = {"step": self._step_seq}
            if tids:
                st.annotate("trace_ids", tids)
            # launch: the host hands the decode program its arguments and
            # gets futures back; fetch: the host waits for the chip
            with st.phase("device", "llm.decode.launch",
                          dict(at, trace_ids=tids) if tids else at) as launch:
                nxt, = self._decode(self._toks, self._bt, self._pos)
            with st.phase("device", "llm.decode.fetch", at) as fetch:
                nxt, = self._result(nxt)
                self._observe_experts(nxt[self.max_running:], fetch.args)
        with telemetry.span("llm.emit", args={"tokens": len(active)}):
            self.metrics.count("decode_steps")
            self.metrics.decode_ms.observe(
                (launch.dur_s + fetch.dur_s) * 1e3)
            self.metrics.tokens_decode.inc(len(active))
            self._observe_tok_s(len(active))
            for i in active:
                lane = self._lanes[i]
                tok = int(nxt[i])
                lane.pos += 1
                lane.last_token = tok
                if not self._push_token(lane, tok):
                    self._release(lane, i)
                    continue
                if self._retire_if_done(lane, lane_idx=i):
                    continue
                self._pos[i] = lane.pos
                self._toks[i, 0] = tok
            self.metrics.lanes_active.set(
                sum(1 for ln in self._lanes if ln is not None))

    def _draft_verify(self, prev, toks, bt, pos):
        """The two programs of a speculative round: the draft proposes
        from ``prev`` and ``toks``, the target verifies. Returns the
        verify program's ``[out_toks, n_acc]``."""
        d_toks, d_lgs = self._draft_step(prev, toks, bt, pos)
        return self._verify(toks, d_toks, d_lgs, bt, pos)

    def _spec_step(self, active: List[int]) -> None:
        """One speculative round over the whole lane set: the draft
        proposes K tokens per lane (K+1 small-model steps in one
        program), the target verifies ALL of them in one batched
        (R, K+1) forward with exact rejection sampling — each live lane
        advances by ``n_acc + 1`` tokens per round instead of 1.
        Inactive lanes ride along pointed at the trash block (their
        outputs are garbage the loop below never reads)."""
        self._step_seq += 1
        with telemetry.step("llm_spec", self._step_seq) as st:
            tids = self._lane_trace_ids(active)
            at = {"step": self._step_seq}
            if tids:
                st.annotate("trace_ids", tids)
            with st.phase("device", "llm.decode.launch",
                          dict(at, trace_ids=tids) if tids else at) as launch:
                # the draft-verify splice chaos site: an injected fault
                # propagates to _fault(), which fails the in-flight
                # requests typed-transient and keeps the engine serving
                chaos.site("serving.llm.verify", lanes=len(active))
                out, n_acc = self._draft_verify(self._prev, self._toks,
                                                self._bt, self._pos)
            with st.phase("device", "llm.decode.fetch", at) as fetch:
                out, n_acc = self._result(out, n_acc)
        with telemetry.span("llm.emit") as emit:
            step_ms = (launch.dur_s + fetch.dur_s) * 1e3
            self.metrics.count("spec_steps")
            self.metrics.count("decode_steps")
            self.metrics.decode_ms.observe(step_ms)
            self.metrics.spec_ms.observe(step_ms)
            emitted_total = 0
            accepted_total = 0
            for i in active:
                lane = self._lanes[i]
                n_take = int(n_acc[i]) + 1
                accepted_total += int(n_acc[i])
                prev_last = lane.last_token
                gone = False
                emitted = 0
                for j in range(n_take):
                    tok = int(out[i, j])
                    emitted += 1
                    lane.last_token = tok
                    if not self._push_token(lane, tok):
                        self._release(lane, i)
                        gone = True
                        break
                    if self._retire_if_done(lane, lane_idx=i):
                        gone = True
                        break
                emitted_total += emitted
                if gone:
                    continue
                # full window emitted: KV for [last, d_0..d_{n_acc-1}] is
                # resident at pos..pos+n_acc; the corrected/bonus token is
                # the new last (written next round); the token at the new
                # pos-1 (the draft catch-up input) is the last ACCEPTED one
                lane.pos += n_take
                self._pos[i] = lane.pos
                self._toks[i, 0] = lane.last_token
                self._prev[i, 0] = (int(out[i, n_take - 2]) if n_take >= 2
                                    else prev_last)
            self.metrics.observe_spec(self._draft_k * len(active),
                                      accepted_total)
            emit.args["tokens"] = emitted_total
            if emitted_total:
                self.metrics.tokens_decode.inc(emitted_total)
                self._observe_tok_s(emitted_total)
            self.metrics.lanes_active.set(
                sum(1 for ln in self._lanes if ln is not None))

    def _push_token(self, lane: _Lane, tok: int) -> bool:
        """Record + stream one token. Returns False when the request's
        ``on_token`` callback raised — the request is failed (typed
        FATAL: a client bug, not a serving fault) and contained to its
        own lane; other lanes keep decoding."""
        lane.req.tokens.append(tok)
        cb = lane.req.on_token
        if cb is None:
            return True
        try:
            cb(tok)
            return True
        except Exception as e:  # noqa: BLE001 — client code
            err = FatalError(f"on_token callback raised: {e!r}")
            err.__cause__ = e
            lane.req.fail(err)
            self.metrics.count("failed")
            return False

    def _retire_if_done(self, lane: _Lane, lane_idx: Optional[int]) -> bool:
        req = lane.req
        done = (len(req.tokens) >= req.max_new_tokens
                or req.tokens[-1] == req.eos_token)
        if not done:
            return False
        self._release(lane, lane_idx)
        req.finish(onp.asarray(req.tokens, onp.int32))
        self.metrics.count("completed")
        return True

    def _release(self, lane: _Lane, lane_idx: Optional[int]) -> None:
        """Drop the lane's block references the moment its sequence
        finishes; a block returns to the free list only when its
        refcount hits zero (prefix-cache residents and other lanes
        sharing a prompt prefix keep theirs alive)."""
        self._kv.release(lane.blocks)
        lane.blocks = []
        if lane_idx is not None:
            self._lanes[lane_idx] = None
            self._bt[lane_idx, :] = self._kv.trash
            self._pos[lane_idx] = 0
            self._toks[lane_idx, 0] = 0
            self._prev[lane_idx, 0] = 0

    # -- fault handling ----------------------------------------------------
    def _fault(self, exc: Exception) -> bool:
        """Type the fault through the resilience classifier, fail every
        in-flight request with it, reset the pool (donated buffers may
        be gone). Returns False (stop the scheduler) on FATAL."""
        with self._state_lock, self._mesh_ctx():
            # a caller-thread warmup() must not interleave the rebuild
            return self._fault_locked(exc)

    def _fault_locked(self, exc: Exception) -> bool:
        kind = classify(exc)
        typed = _typed(exc, f"LLM scheduler fault ({kind})")
        self.metrics.count("resets")
        fatal = kind != TRANSIENT
        if fatal:
            # flip to broken BEFORE any request observes its failure —
            # a caller woken by req.fail must find submit() shedding
            self._broken = typed
            self._queue.close()
        for i, lane in enumerate(self._lanes):
            if lane is not None:
                self._release(lane, i)
                lane.req.fail(typed)
                self.metrics.count("failed")
        # the failed program call may have consumed donated pool
        # buffers: rebuild them (zeroed — no live lanes remain)
        self._kv.reset()
        self.metrics.lanes_active.set(0)
        if not fatal:
            return True                 # keep serving new requests
        n = self._queue.fail_all(lambda: ServerOverload(
            f"LLM engine stopped on a fatal fault: {typed!r}"))
        self.metrics.count("failed", n)
        # post-mortem with the lane/pool gauges in it (no-op unarmed)
        telemetry.flight.try_dump("llm_fatal")
        return False

    # -- misc --------------------------------------------------------------
    def _next_key(self):
        if self._greedy:
            return self._key
        self._key, sub = jax.random.split(self._key)
        return sub

    def _observe_tok_s(self, n: int) -> None:
        now = time.monotonic()
        w = self._tok_window
        w.append((now, n))
        while w and now - w[0][0] > 5.0:
            w.pop(0)
        span = now - w[0][0] if len(w) > 1 else 0.0
        if span > 0:
            self.metrics.tok_s.set(sum(x[1] for x in w[1:]) / span)

    def _record_manifest(self, prog: _Program, args) -> None:
        """Decode-frontier warmup manifest: every compiled program's
        signature (+ AOT store key when the persistent cache is armed)
        so replicas replay exactly this frontier (``engine.warmup``,
        ``tools/aot_warmup.py --manifest``). Called by a program's first
        call, with its arguments. Best-effort: must never fail a served
        step."""
        entry = {"label": prog.label, "bucket": int(prog.bucket),
                 "dtype": str(self._kv.dtype)}
        try:
            key = getattr(prog.run, "resolved_key",
                          lambda *a: None)(*args)
            if key:
                entry["key"] = key
        except Exception:  # noqa: BLE001
            pass
        self._warmup_manifest.record(**entry)
        self.metrics.count("compiles")

    # -- warmup / manifests ------------------------------------------------
    def warmup(self, prompt_lengths=None, manifest=None) -> List[int]:
        """Pre-compile the decode program and the prefill buckets so the
        first real traffic pays no cold compiles (with
        ``MXNET_TPU_AOT_CACHE`` armed, compiles resolve from the
        persistent store — the zero-cold-compile replica scale-up path).

        ``prompt_lengths``: iterable of representative prompt lengths
        (default: one, ``block_size``); ``manifest``: a
        :class:`~mxnet_tpu.aot.WarmupManifest` (or path) recorded by a
        previous engine — replays exactly its prefill-bucket frontier.
        Returns the warmed prefill buckets."""
        from .. import aot

        if self._chunk:
            buckets = []        # one chunk program, whatever the lengths
        elif manifest is not None:
            if not isinstance(manifest, aot.WarmupManifest):
                manifest = aot.WarmupManifest.load(manifest)
            buckets = sorted({int(e["bucket"])
                              for e in manifest.entries()
                              if e.get("label") == "llm.prefill"
                              and e.get("bucket")})
        else:
            lens = (list(prompt_lengths) if prompt_lengths
                    else [self.block_size])
            buckets = sorted({self._prefill_bucket(int(p)) for p in lens})
        # warming is running: one real (trash-table) call per program
        with self._state_lock, self._mesh_ctx():
            self._warmup_buckets_locked(buckets)
        return buckets

    def _warmup_buckets_locked(self, buckets) -> None:
        """Each program that has not run yet, once, on trash tables."""
        trash = self._kv.trash
        if self._chunk and self._chunk_step.fresh:
            # the trash slot: a block's id, or the lane past the last
            self._chunk_step(onp.zeros((1, self._chunk), onp.int32),
                             onp.int32(0), onp.int32(1),
                             *self._chunk_where([trash], self.max_running))
        for b in buckets:
            if self._prefill_program(b).fresh:
                self._full_prefill(onp.zeros((b,), onp.int32), [])
        toks = onp.zeros((self.max_running, 1), onp.int32)
        bt = onp.full((self.max_running, self.max_blocks_per_seq), trash,
                      onp.int32)
        pos = onp.zeros((self.max_running,), onp.int32)
        if self._decode.fresh:
            self._decode(toks, bt, pos)
        if self._spec and self._draft_step.fresh:
            self._draft_verify(toks, toks, bt, pos)

    def warmup_manifest(self):
        """The live decode-frontier manifest (keeps growing)."""
        return self._warmup_manifest

    def save_warmup_manifest(self, path: str) -> str:
        return self._warmup_manifest.save(path)

    # -- stats / lifecycle -------------------------------------------------
    def stats(self) -> Dict:
        from .. import aot

        c = self.metrics.counters()
        out = {
            "counters": c,
            "lanes_active": int(self.metrics.lanes_active.get()),
            "max_running": self.max_running,
            "block_size": self.block_size,
            "pool_blocks_total": self.num_blocks,
            "pool_blocks_free": self._kv.free_blocks,
            "kv_cache_dtype": self._kv.dtype,
            "tok_s": round(float(self.metrics.tok_s.get()), 2),
            "decode_step_ms": self.metrics.decode_ms.summary(),
            "prefill_ms": self.metrics.prefill_ms.summary(),
            "queue_wait_ms": self.metrics.queue_wait_ms.summary(),
            "queue_len": len(self._queue),
            "aot": aot.stats(),
        }
        if self._geom.row_layers is not None:
            out["kv_rows_full"] = int(self.metrics.kv_rows_full.get())
            out["kv_rows_window"] = int(self.metrics.kv_rows_window.get())
        if "moe_assignments" in c:
            out["expert_load_max_over_mean"] = \
                self.metrics.expert_load_ratio.summary()
        if self.role is not None:
            out["role"] = self.role
            out["handoff_exported_blocks"] = int(
                self.metrics.handoff_exported.value)
        if self._mesh is not None:
            from ..parallel.sharding import mesh_topology

            out["sharding"] = {
                "devices": int(self._mesh.devices.size),
                "topology": mesh_topology(self._mesh),
                "pool_bytes_per_device": int(
                    self.metrics.shard_pool_bytes.get()),
            }
        if self._spec:
            out["speculative"] = {
                "draft_k": self._draft_k,
                "proposed": int(self.metrics.spec_proposed.value),
                "accepted": int(self.metrics.spec_accepted.value),
                "draft_acceptance_rate": round(
                    float(self.metrics.draft_acceptance_rate.get()), 4),
            }
        if self._kv.prefix_on:
            out["prefix_cache"] = {
                "cached_blocks": len(self._kv.prefix),
                "hit_requests": self._kv.hit_requests,
                "hit_tokens": int(self.metrics.prefix_hit_tokens.value),
                "miss_tokens": int(self.metrics.prefix_miss_tokens.value),
                "prefix_hit_rate": round(
                    float(self.metrics.prefix_hit_rate.get()), 4),
            }
        if self._kv.spill is not None:
            out["kv_spill"] = self._kv.spill.stats()
        return out

    @property
    def alive(self) -> bool:
        """The scheduler step loop is live: thread running, not stopped
        on a fatal fault, not closed. What the fleet health monitor
        gates the per-replica heartbeat on (a dead loop must go stale,
        a wedged one is caught by :attr:`last_tick` age)."""
        return (self._thread.is_alive() and self._broken is None
                and not self._closed)

    def close(self, drain: bool = True, timeout_s: float = 60.0) -> None:
        """Stop admitting; finish in-flight + queued work
        (``drain=True``) or fail it, then stop the scheduler.

        Never leaves a queued request hanging: if the scheduler cannot
        drain the queue — its thread already exited, or it is wedged
        past ``timeout_s`` — whatever still sits in the admission queue
        is failed typed (:class:`ServerOverload`) so every ``wait()``
        returns."""
        from ..telemetry import exporter as _texporter

        _texporter.unregister_liveness(f"llm:{self.metrics.engine_id}")
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            self._drain = drain
            self._queue.close()
            if not drain:
                self._queue.fail_all(
                    lambda: ServerOverload("engine closed without drain"))
                # lane/pool teardown under the state lock: the scheduler
                # may be mid-tick on these structures
                with self._state_lock:
                    for i, lane in enumerate(self._lanes):
                        if lane is not None:
                            self._release(lane, i)
                            lane.req.fail(ServerOverload(
                                "engine closed without drain"))
        self._thread.join(timeout_s)
        if len(self._queue) and not self._thread.is_alive():
            # the scheduler died (fatal stop raced the close, or a
            # bookkeeping bug killed the thread) with requests still
            # queued: nobody will ever drain them — fail them typed
            # instead of hanging their wait() forever
            n = self._queue.fail_all(lambda: ServerOverload(
                "engine closed with the scheduler already stopped — "
                "queued request failed, resubmit elsewhere"))
            self.metrics.count("failed", n)
        elif len(self._queue) and self._thread.is_alive():
            # drain timed out with the scheduler wedged: the caller is
            # leaving — fail what is still *queued* (in-flight lanes
            # keep their first-completion-wins semantics if the
            # scheduler ever unwedges)
            n = self._queue.fail_all(lambda: ServerOverload(
                f"engine close(drain=True) timed out after "
                f"{timeout_s:g}s with the scheduler wedged — queued "
                "request failed, resubmit elsewhere"))
            self.metrics.count("failed", n)
        # a closed engine carries no load: zero the live-load gauges so
        # a cluster scraper summing this process's exposition does not
        # count ghost throughput/capacity from engines that no longer
        # exist (counters and histograms stay — they are cumulative)
        for g in (self.metrics.tok_s, self.metrics.lanes_active,
                  self.metrics.lanes_total, self.metrics.pool_free,
                  self.metrics.pool_total, self.metrics.kv_spill_blocks,
                  self.metrics.kv_spill_bytes, self.metrics.shard_devices,
                  self.metrics.shard_pool_bytes, self.metrics.kv_rows_full,
                  self.metrics.kv_rows_window):
            g.set(0)
        self._kv.close()

    def __enter__(self) -> "LLMEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
