"""Autoregressive generation with a KV cache.

The reference has no in-tree generation loop (gluonnlp's beam search ran
eager per-step graphs). TPU-first design: prefill and decode are each ONE
compiled XLA program — the decode step runs under ``lax.scan`` with a
preallocated (L, B, H, Lmax, D) cache updated by ``dynamic_update_slice``,
so generating N tokens costs one compile + one device program, not N
dispatches. Sampling (greedy / temperature / top-k) and beam reordering
happen on device inside the scan.
"""
from __future__ import annotations

import dataclasses
import threading
import weakref
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as onp

from ...base import MXNetError
from ...ndarray.ndarray import ndarray, _unwrap, _wrap
from ...ops.nn import kv_pool_rows
from ..block import HybridBlock

__all__ = ["generate", "beam_search", "paged_decode_program",
           "paged_prefill_program", "paged_suffix_prefill_program",
           "paged_spec_draft_program", "paged_spec_verify_program",
           "CacheGeometry", "state_prefill_program"]


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """What a model's ``cache_geometry(block_size)`` tells the serving
    engine's cache manager about the pools of ``init_block_pool``: the
    engine allocates, the model says what there is to allocate.

    ``kind``: ``"kv_blocks"`` — blocks of ``block_size`` K/V rows, a
    request holds as many as its tokens fill — or ``"state_slots"`` — a
    block is one request's whole recurrent state. ``lane_state``: beside
    its blocks the model keeps a state that every lane holds for its
    whole life — ``init_block_pool(..., state_slots=)`` then returns the
    pools the blocks index first (K, V) and after them the pools the
    lane's own index does; the programs take and give back all of them,
    the chunk program also the lane's slot and table, and a slot handed
    to a new request starts from zero inside its first chunk's program.
    ``blocks_for(tokens)``:
    the pool blocks a request of that many tokens reserves (which is also
    the width of a lane's block table, at ``max_context``).
    ``max_positions``: the model's context window, or None.
    ``prefill_chunk``: None for whole-prompt prefill in length buckets
    (``paged_prefill_program``), else the one chunk size of
    ``state_prefill_program``. ``cache_dtypes``: the pool dtypes the model
    can hold (None: those of ``_resolve_cache_dtype``); the first is its
    default. ``unsupported``: engine feature -> why this cache cannot
    carry it yet. ``row_layers``: where the layers that keep K/V rows are
    of two kinds, ``(full layers, window layers, window)`` — a full layer
    keeps every position's row in the request's blocks (what
    ``blocks_for`` counts), a window layer the last ``window`` rows in a
    ring that is one of the lane's pools; the engine's gauges of the rows
    held by family read it."""
    kind: str
    blocks_for: Callable[[int], int]
    max_positions: Optional[int] = None
    prefill_chunk: Optional[int] = None
    cache_dtypes: Optional[tuple] = None
    unsupported: dict = dataclasses.field(default_factory=dict)
    lane_state: bool = False
    row_layers: Optional[tuple] = None


class _StepAdapter(HybridBlock):
    """Exposes model.decode_step as a plain forward so ``functionalize``
    can turn it into a pure jittable function."""

    def __init__(self, model):
        super().__init__()
        self.model = model

    def forward(self, tokens, cache_k, cache_v, pos):
        return self.model.decode_step(tokens, cache_k, cache_v, pos)


class _PagedStepAdapter(HybridBlock):
    """Same, for model.decode_step_paged (block-pool decode)."""

    def __init__(self, model):
        super().__init__()
        self.model = model

    def forward(self, tokens, *pools_table_positions):
        return self.model.decode_step_paged(tokens, *pools_table_positions)


_DECODE_CACHE_MAX = 32
# model -> {ckey: jitted program}; a WeakKeyDictionary so cached programs
# die with the model and NOTHING is stored on the model itself (pickling
# any model type keeps working — no lock/jit objects in __dict__)
_DECODE_CACHES = weakref.WeakKeyDictionary()
# model -> (param-identity key, (qparams, scales), param refs): the
# weight-only-int8 tree, re-quantized only when the weights change
_INT8W_CACHES = weakref.WeakKeyDictionary()
_DECODE_CACHES_LOCK = threading.RLock()


def _decode_jit_entries(model):
    """Test/introspection hook: the live decode-program cache for a model."""
    with _DECODE_CACHES_LOCK:
        return dict(_DECODE_CACHES.get(model) or {})


def _decode_cache(model, ckey):
    """LRU-bounded per-model cache of compiled decode programs. Returns
    (store_fn, cached_or_None); the lock covers check→insert so concurrent
    same-config callers share one program instead of compiling twice."""
    with _DECODE_CACHES_LOCK:
        cache = _DECODE_CACHES.get(model)
        if cache is None:
            cache = _DECODE_CACHES[model] = {}
        fn = cache.get(ckey)
        if fn is not None:
            cache[ckey] = cache.pop(ckey)  # LRU bump

    def store(jrun):
        with _DECODE_CACHES_LOCK:
            got = cache.get(ckey)
            if got is not None:  # another thread won the race
                return got
            cache[ckey] = jrun
            while len(cache) > _DECODE_CACHE_MAX:
                cache.pop(next(iter(cache)))
            return jrun

    return store, fn


def _sample(logits, key, greedy, temperature, top_k):
    """Pick next tokens from (B, V) logits, on device."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


_KV_CACHE_DTYPES = (None, "int8", "float32", "bfloat16", "float16")


def _resolve_cache_dtype(model, kv_cache_dtype):
    """Validate + default the KV cache dtype (shared by the dense
    generate()/beam_search() path and the paged serving programs)."""
    if kv_cache_dtype not in _KV_CACHE_DTYPES:
        # an unknown integer dtype would silently truncate K/V to garbage
        # through the non-quantized astype path — must be loud
        raise MXNetError(
            f"kv_cache_dtype {kv_cache_dtype!r} not supported "
            "(int8/float32/bfloat16/float16)")
    return kv_cache_dtype or (
        onp.dtype(model.word_embed.weight.dtype).name
        if hasattr(model, "word_embed") else "float32")


def _prep(model, prompt_ids, max_new_tokens, max_length,
          kv_cache_dtype=None):
    """Shared decode setup: wrap the prompt, validate lengths against the
    model's context window (jax dynamic_slice CLAMPS out-of-range starts,
    so decoding past the position table would silently reuse the last
    embedding — must be an error), allocate model-dtype caches, and
    functionalize one shape-generic step fn (it serves both the (B, P)
    prefill and every (B, 1) decode step)."""
    from ... import numpy as mxnp

    prompt = prompt_ids if isinstance(prompt_ids, ndarray) \
        else mxnp.array(onp.asarray(prompt_ids, onp.int32))
    b, p = prompt.shape
    lmax = max_length or (p + max_new_tokens)
    if lmax < p + max_new_tokens:
        raise MXNetError(
            f"max_length {lmax} < prompt {p} + max_new_tokens "
            f"{max_new_tokens}")
    pos_table = getattr(model, "pos_embed", None)
    if pos_table is not None and lmax > pos_table.shape[0]:
        raise MXNetError(
            f"generation length {lmax} exceeds the model's context window "
            f"(max_length={pos_table.shape[0]})")
    cache_dtype = _resolve_cache_dtype(model, kv_cache_dtype)
    ck, cv = model.init_cache(b, lmax, dtype=cache_dtype)
    adapter = _StepAdapter(model)
    pos0 = mxnp.array(onp.zeros((), onp.int32))
    step_fn, params = adapter.functionalize(prompt, ck, cv, pos0)
    return prompt, b, p, lmax, ck, cv, step_fn, params


def _apply_weight_dtype(model, step_fn, params, weight_dtype):
    """Optional weight-only int8 for the decode program (VERDICT r4
    item #3 pivot): weights are stored int8 + per-output-channel scales
    in the params pytree and dequantized INSIDE the compiled step, so
    every decode token reads half the weight HBM bytes of bf16. Scales
    travel in the pytree (not closures): the memoized compiled program
    stays correct when the model's weights change between calls.

    The quantized tree is memoized on the model per weight VERSION
    (keyed on the identity of every param buffer, with refs held so ids
    stay valid): quantization is several full-precision passes over all
    weights and must not run per generate() call — that would put the
    quantizer inside every measured decode."""
    if weight_dtype is None:
        return step_fn, params
    if weight_dtype != "int8":
        raise MXNetError(
            f"weight_dtype {weight_dtype!r} not supported (int8)")
    from ...contrib.quantization import (dequantize_weights_int8,
                                         quantize_weights_int8)

    key = tuple((k, id(v)) for k, v in sorted(params.items()))
    with _DECODE_CACHES_LOCK:
        cached = _INT8W_CACHES.get(model)
    if cached is not None and cached[0] == key:
        q, scales = cached[1]
    else:
        q, scales = quantize_weights_int8(params)
        with _DECODE_CACHES_LOCK:
            # the params list ref keeps the keyed buffers alive, so a
            # freed buffer's id can never be recycled into a false hit;
            # weak-keyed off-model storage (the _DECODE_CACHES rule:
            # nothing lands in model.__dict__, pickling keeps working)
            _INT8W_CACHES[model] = (key, (q, scales),
                                    list(params.values()))
    wrapped = {"__int8_weights__": q, "__int8_scales__": scales}

    def qstep(p, *rest):
        deq = dequantize_weights_int8(p["__int8_weights__"],
                                      p["__int8_scales__"])
        return step_fn(deq, *rest)

    return qstep, wrapped


def generate(model, prompt_ids, max_new_tokens: int,
             max_length: Optional[int] = None, greedy: bool = True,
             temperature: float = 1.0, top_k: int = 0, eos_token: int = -1,
             seed: int = 0, kv_cache_dtype: Optional[str] = None,
             weight_dtype: Optional[str] = None):
    """Generate ``max_new_tokens`` continuations of ``prompt_ids`` (B, P).

    ``model`` must provide ``decode_step``/``init_cache`` (the causal LM
    contract, :class:`~mxnet_tpu.gluon.model_zoo.bert._CausalLM`). Returns
    an (B, max_new_tokens) int32 ndarray. ``eos_token``: once a sequence
    has emitted it, remaining positions repeat it (the scan still runs to
    length — static shapes — but the output is clean).
    ``kv_cache_dtype="int8"`` stores the KV cache quantized (per-token
    per-head scales): half the HBM bytes of bf16 on the bandwidth-bound
    decode read path, at ~0.4% rms dequant error.
    """
    prompt, b, p, lmax, ck, cv, step_fn, params = _prep(
        model, prompt_ids, max_new_tokens, max_length, kv_cache_dtype)
    step_fn, params = _apply_weight_dtype(model, step_fn, params,
                                          weight_dtype)

    # Memoize the compiled program per model: a fresh closure every
    # call would miss jax.jit's trace cache and recompile each generate()
    # (observed as a ~20s "decode" on TPU). The cached trace is reusable
    # because step_fn is pure — current weights enter through ``params``.
    # Key on the RESOLVED length (max_length=None and max_length=p+new are
    # the same program) and drop sampling knobs that are dead under greedy.
    tkey = (0.0, 0) if greedy else (float(temperature), int(top_k))
    ckey = ("generate", b, p, max_new_tokens, lmax, greedy, *tkey,
            int(eos_token), kv_cache_dtype, weight_dtype)
    store, cached = _decode_cache(model, ckey)
    if cached is not None:
        out = cached(params, _unwrap(prompt), _unwrap(ck), _unwrap(cv),
                     jax.random.PRNGKey(seed))
        return _wrap(out)

    def run(params, prompt_v, ck_v, cv_v, key):
        (logits, ck_v, cv_v), _ = step_fn(
            params, prompt_v, ck_v, cv_v, jnp.zeros((), jnp.int32))
        key, sub = jax.random.split(key)
        first = _sample(logits[:, -1], sub, greedy, temperature, top_k)
        done = first == eos_token

        def body(carry, _):
            tok, ck_c, cv_c, pos, key_c, done_c = carry
            (step_logits, ck_c, cv_c), _ = step_fn(
                params, tok[:, None], ck_c, cv_c, pos)
            key_c, sub_c = jax.random.split(key_c)
            nxt = _sample(step_logits[:, -1], sub_c, greedy, temperature,
                          top_k)
            nxt = jnp.where(done_c, eos_token, nxt)
            done_c = done_c | (nxt == eos_token)
            return (nxt, ck_c, cv_c, pos + 1, key_c, done_c), nxt

        carry = (first, ck_v, cv_v, jnp.asarray(p, jnp.int32), key, done)
        if max_new_tokens > 1:
            _, rest = jax.lax.scan(body, carry, None,
                                   length=max_new_tokens - 1)
            return jnp.concatenate([first[:, None], rest.T], axis=1)
        return first[:, None]

    jrun = store(jax.jit(run))
    out = jrun(params, _unwrap(prompt), _unwrap(ck), _unwrap(cv),
               jax.random.PRNGKey(seed))
    return _wrap(out)


def beam_search(model, prompt_ids, max_new_tokens: int, beam_size: int = 4,
                max_length: Optional[int] = None, alpha: float = 1.0,
                eos_token: int = -1,
                kv_cache_dtype: Optional[str] = None,
                weight_dtype: Optional[str] = None):
    """Beam-search decoding (the gluonnlp-era capability, re-built
    TPU-first): ONE ``lax.scan`` whose carry holds the (L, B*K, H, Lmax, D)
    KV caches; beam reordering is a batched gather on the cache's beam
    axis inside the compiled program — no host round trips.

    Returns ``(sequences, scores)``: (B, K, max_new_tokens) int32 ordered
    best-first, and (B, K) length-normalized log-probs
    (``score = logp / len**alpha``; ``alpha=0`` gives raw joint log-prob).
    """
    k = beam_size
    # caches allocated at batch B: prefill runs un-tiled, the K-fold tile
    # happens on device from the prefill result (no B*K zero buffers ever
    # cross host->device)
    prompt, b, p, lmax, ck, cv, step_fn, params = _prep(
        model, prompt_ids, max_new_tokens, max_length, kv_cache_dtype)
    step_fn, params = _apply_weight_dtype(model, step_fn, params,
                                          weight_dtype)

    neg_inf = -1e9

    # same memoization as generate(): one compiled program per static
    # decode config, current weights flow through ``params``
    ckey = ("beam", b, p, max_new_tokens, lmax, k, float(alpha),
            int(eos_token), kv_cache_dtype, weight_dtype)
    store, cached = _decode_cache(model, ckey)
    if cached is not None:
        seqs, scores = cached(params, _unwrap(prompt), _unwrap(ck),
                              _unwrap(cv))
        return _wrap(seqs), _wrap(scores)

    def run(params, prompt_v, ck_v, cv_v):
        (logits, ck_s, cv_s), _ = step_fn(
            params, prompt_v, ck_v, cv_v, jnp.zeros((), jnp.int32))
        logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
        vocab = logp0.shape[-1]
        scores, first = jax.lax.top_k(logp0, k)          # (B, K)
        first = first.astype(jnp.int32)

        def tile(c):  # (L, B, ...) -> (L, B*K, ...)
            reps = (1, 1, k) + (1,) * (c.ndim - 2)
            return jnp.tile(c[:, :, None], reps).reshape(
                c.shape[0], b * k, *c.shape[2:])

        ck_t, cv_t = tile(ck_s), tile(cv_s)
        done = first == eos_token
        seqs = jnp.zeros((b, k, max_new_tokens), jnp.int32)
        seqs = seqs.at[:, :, 0].set(first)
        lengths = jnp.ones((b, k), jnp.int32)

        def body(carry, step):
            tok, ck_c, cv_c, pos, scores_c, done_c, seqs_c, len_c = carry
            (lg, ck_c, cv_c), _ = step_fn(
                params, tok.reshape(b * k, 1), ck_c, cv_c, pos)
            logp = jax.nn.log_softmax(
                lg[:, -1].astype(jnp.float32)).reshape(b, k, vocab)
            # finished beams: force eos continuation at zero added cost,
            # everything else -inf so they never fork
            eos_ix = jnp.clip(eos_token, 0, vocab - 1)
            frozen = jnp.full((vocab,), neg_inf).at[eos_ix].set(0.0)
            logp = jnp.where(done_c[:, :, None], frozen[None, None], logp)
            total = scores_c[:, :, None] + logp          # (B, K, V)
            flat = total.reshape(b, k * vocab)
            new_scores, idx = jax.lax.top_k(flat, k)     # (B, K)
            parent = (idx // vocab).astype(jnp.int32)    # which beam
            new_tok = (idx % vocab).astype(jnp.int32)

            def reorder_cache(c):
                cs = c.reshape(c.shape[0], b, k, *c.shape[2:])
                cs = jnp.take_along_axis(
                    cs, parent[None, :, :, None, None, None], axis=2)
                return cs.reshape(c.shape[0], b * k, *c.shape[2:])

            ck_c = reorder_cache(ck_c)
            cv_c = reorder_cache(cv_c)
            done_c = jnp.take_along_axis(done_c, parent, axis=1)
            len_c = jnp.take_along_axis(len_c, parent, axis=1)
            seqs_c = jnp.take_along_axis(seqs_c, parent[:, :, None], axis=1)
            seqs_c = seqs_c.at[:, :, step].set(
                jnp.where(done_c, eos_token, new_tok))
            len_c = len_c + (~done_c).astype(jnp.int32)
            done_c = done_c | (new_tok == eos_token)
            return (new_tok, ck_c, cv_c, pos + 1, new_scores, done_c,
                    seqs_c, len_c), None

        carry = (first, ck_t, cv_t, jnp.asarray(p, jnp.int32), scores,
                 done, seqs, lengths)
        if max_new_tokens > 1:
            carry, _ = jax.lax.scan(
                body, carry, jnp.arange(1, max_new_tokens))
        _, _, _, _, scores_f, _, seqs_f, len_f = carry
        norm = jnp.power(len_f.astype(jnp.float32), alpha)
        final = scores_f / jnp.maximum(norm, 1.0)
        order = jnp.argsort(-final, axis=1)
        return (jnp.take_along_axis(seqs_f, order[:, :, None], axis=1),
                jnp.take_along_axis(final, order, axis=1))

    jrun = store(jax.jit(run))
    seqs, scores = jrun(params, _unwrap(prompt), _unwrap(ck), _unwrap(cv))
    return _wrap(seqs), _wrap(scores)


# --- paged (block-pool) decode programs ------------------------------------
# The continuous-batching serving engine (mxnet_tpu.serving.llm) runs two
# compiled programs built here: ONE decode step over the whole lane set
# (fixed (max_running, 1) shape — admission/retirement/growth change array
# CONTENT, never shapes, so the engine never retraces), and one prefill-
# and-splice program per pow2 prompt bucket. Both are memoized through the
# same per-model _decode_cache (and compiled through aot.cached_jit, so an
# armed MXNET_TPU_AOT_CACHE store serves them to fresh replicas with zero
# cold compiles).

def _paged_jit(fn, label, donate, store):
    """Compile ``fn`` at the AOT seam and memoize through the decode
    cache: a plain jax.jit when no persistent store is armed."""
    from ... import aot

    return store(aot.cached_jit(fn, label=label,
                                donate_argnums=donate))


def _with_counts(tokens, counts):
    """The sampled tokens and, behind them, what the model's step counted
    on the device (none, or one int32 vector): one array, one fetch."""
    if not counts:
        return tokens
    return jnp.concatenate([jnp.reshape(tokens, (-1,)),
                            counts[0].astype(jnp.int32)])


def paged_decode_program(model, *, max_running, num_blocks, block_size,
                         max_blocks_per_seq, kv_cache_dtype=None,
                         weight_dtype=None, greedy=True, temperature=1.0,
                         top_k=0, donate=False):
    """Build (or fetch memoized) the ONE fixed-shape continuous-batching
    decode step for ``model``.

    Returns ``(run, params)``: ``run(params, tokens (R,1) i32, pool_k,
    pool_v, block_table (R,MB) i32, positions (R,) i32, key) ->
    (next_tokens (R,) i32, new_pool_k, new_pool_v)`` — with however many
    pools the model's ``init_block_pool`` returns in the pair's place,
    and, where the model's step returns counters between its logits and
    its pools (int32, made on the device), those behind the tokens in
    the one array the host fetches. Lane ``r``'s token
    is written at ``positions[r]`` through its block table, attended
    through the pool, and sampled (greedy argmax by default). Inactive
    lanes must point at a trash block — their outputs are garbage the
    scheduler ignores. With ``donate=True`` the pool buffers are donated
    and decode reuses them in place: every layer stores its rows into
    the whole ``(L, NB, bs, H*D')`` pool and the kernel reads blocks of
    that same buffer, one row-major layout for all three, so the
    compiled step holds no temporary and no copy shaped like a pool
    (``tests/test_chip_compile.py`` asks the chip's compiler).
    """
    cache_dtype = _resolve_cache_dtype(model, kv_cache_dtype)
    r, mb = int(max_running), int(max_blocks_per_seq)
    from ... import numpy as mxnp

    # functionalize only finalizes PARAMETER shapes — the step fn is
    # shape-generic and jit traces at first call with the engine's real
    # pool, so a 2-block template avoids transiently holding a second
    # full-size pool (which for an HBM-sized pool would double KV
    # memory at engine startup)
    pools0 = model.init_block_pool(min(int(num_blocks), 2), block_size,
                                   dtype=cache_dtype)
    n = len(pools0)
    tokens0 = mxnp.array(onp.zeros((r, 1), onp.int32))
    bt0 = mxnp.array(onp.zeros((r, mb), onp.int32))
    pos0 = mxnp.array(onp.zeros((r,), onp.int32))
    adapter = _PagedStepAdapter(model)
    step_fn, params = adapter.functionalize(tokens0, *pools0, bt0, pos0)
    step_fn, params = _apply_weight_dtype(model, step_fn, params,
                                          weight_dtype)
    tkey = (0.0, 0) if greedy else (float(temperature), int(top_k))
    ckey = ("paged_decode", r, int(num_blocks), int(block_size), mb,
            bool(greedy), *tkey, cache_dtype, weight_dtype, bool(donate))
    store, cached = _decode_cache(model, ckey)
    if cached is not None:
        return cached, params

    def run(params, tokens, *pools_table_positions_key):
        (logits, *counts_pools), _ = step_fn(
            params, tokens, *pools_table_positions_key[:-1])
        nxt = _sample(logits[:, -1], pools_table_positions_key[-1], greedy,
                      temperature, top_k)
        return (_with_counts(nxt, counts_pools[:-n]), *counts_pools[-n:])

    jrun = _paged_jit(run, "llm.decode",
                      tuple(range(2, 2 + n)) if donate else (), store)
    return jrun, params


def paged_prefill_program(model, *, prefill_len, num_blocks, block_size,
                          kv_cache_dtype=None, weight_dtype=None,
                          greedy=True, temperature=1.0, top_k=0,
                          donate=False):
    """Build (or fetch memoized) the prefill-and-splice program for one
    prompt-length bucket.

    Returns ``(run, params)``: ``run(params, prompt (1, Pb) i32,
    last_idx () i32, pool_k, pool_v, block_ids (Pb//bs,) i32, key) ->
    (first_token () i32, new_pool_k, new_pool_v)``. The prompt (padded
    to the ``Pb`` bucket) prefills a dense per-request cache allocated
    INSIDE the program, the cache is resliced into ``Pb // block_size``
    blocks of pool rows (``(Lyr, nb, bs, H*D')``, the pool's own layout)
    and spliced into the running pool at ``block_ids`` (in place when
    the pools are donated: the same rule as decode), and the
    first generated token is sampled from the logits at ``last_idx``
    (the last REAL prompt position — pad garbage beyond it never
    matters: causal attention keeps it out of positions <= last_idx and
    the decode-side length mask keeps it out of every later step).
    Entries of ``block_ids`` past the prompt's real blocks should point
    at a trash block."""
    cache_dtype = _resolve_cache_dtype(model, kv_cache_dtype)
    pb = int(prefill_len)
    bs = int(block_size)
    if pb % bs:
        raise MXNetError(
            f"prefill bucket {pb} must be a multiple of block_size {bs}")
    nb = pb // bs
    from ... import numpy as mxnp

    ck, cv = model.init_cache(1, pb, dtype=cache_dtype)
    cache_shape = tuple(ck.shape)       # (Lyr, 1, H, Pb, D')
    cache_jdtype = _unwrap(ck).dtype
    prompt0 = mxnp.array(onp.zeros((1, pb), onp.int32))
    pos0 = mxnp.array(onp.zeros((), onp.int32))
    adapter = _StepAdapter(model)
    step_fn, params = adapter.functionalize(prompt0, ck, cv, pos0)
    step_fn, params = _apply_weight_dtype(model, step_fn, params,
                                          weight_dtype)
    tkey = (0.0, 0) if greedy else (float(temperature), int(top_k))
    ckey = ("paged_prefill", pb, int(num_blocks), bs, bool(greedy),
            *tkey, cache_dtype, weight_dtype, bool(donate))
    store, cached = _decode_cache(model, ckey)
    if cached is not None:
        return cached, params

    lyr, _, heads, _, dp = cache_shape

    def run(params, prompt, last_idx, pool_k, pool_v, block_ids, key):
        ck0 = jnp.zeros(cache_shape, cache_jdtype)
        cv0 = jnp.zeros(cache_shape, cache_jdtype)
        (logits, ck_f, cv_f), _ = step_fn(
            params, prompt, ck0, cv0, jnp.zeros((), jnp.int32))

        def blocks(c):          # (Lyr,1,H,Pb,D') -> (Lyr,nb,bs,H*D') rows
            return kv_pool_rows(
                c[:, 0].transpose(0, 2, 1, 3)).reshape(lyr, nb, bs,
                                                       heads * dp)

        pool_k = pool_k.at[:, block_ids].set(blocks(ck_f))
        pool_v = pool_v.at[:, block_ids].set(blocks(cv_f))
        first = _sample(logits[:, last_idx], key, greedy, temperature,
                        top_k)[0]
        return first, pool_k, pool_v

    jrun = _paged_jit(run, "llm.prefill", (3, 4) if donate else (), store)
    return jrun, params


class _ChunkStepAdapter(HybridBlock):
    """Same, for model.prefill_chunk_step (one chunk of one lane)."""

    def __init__(self, model):
        super().__init__()
        self.model = model

    def forward(self, tokens, *pools_where_start_n):
        return self.model.prefill_chunk_step(tokens, *pools_where_start_n)


def state_prefill_program(model, *, chunk, num_blocks, block_size=0,
                          max_blocks_per_seq=None, kv_cache_dtype=None,
                          greedy=True, temperature=1.0, top_k=0,
                          donate=False):
    """Build (or fetch memoized) the ONE prefill program of a model that
    carries a state (``CacheGeometry.prefill_chunk``): a chunk
    of ``chunk`` tokens of one lane that carries the lane's state, so a
    prompt of any length is a loop over it — no length buckets, one
    warm-up shape.

    Returns ``(run, params)``: ``run(params, tokens (1, chunk) i32, start
    () i32, n_real () i32, pool_s, pool_z, slot () i32, key) ->
    (next_token () i32, new_pool_s, new_pool_z)``. The chunk's tokens sit
    at positions ``start + arange(chunk)``; the first ``n_real`` are
    tokens, the rest padding that neither decays nor adds to the state.
    The slot counts as zero where ``start == 0`` — a slot handed to a new
    request needs no clearing. ``next_token`` is sampled from the logits
    of the last real row alone (it is the request's first token after its
    last chunk, and means nothing before).

    A model that keeps its state *beside* blocks of K/V rows
    (``CacheGeometry.lane_state``; ``max_blocks_per_seq`` given) has all
    its pools where the two stand, and its chunk also writes the rows of
    its tokens through the lane's table: ``run(params, tokens, start,
    n_real, *pools, slot, table (MB,) i32, key)``. Counters the step
    returns come behind the token, as in :func:`paged_decode_program`."""
    from ... import numpy as mxnp

    cc = int(chunk)
    where = [mxnp.array(onp.zeros((), onp.int32))]      # the slot
    if max_blocks_per_seq is None:
        pools0 = model.init_block_pool(min(int(num_blocks), 2), 0)
        ckey = ()
    else:
        cache_dtype = _resolve_cache_dtype(model, kv_cache_dtype)
        pools0 = model.init_block_pool(min(int(num_blocks), 2),
                                       int(block_size), dtype=cache_dtype)
        where.append(mxnp.array(onp.zeros((int(max_blocks_per_seq),),
                                          onp.int32)))
        ckey = (int(block_size), int(max_blocks_per_seq), cache_dtype)
    n, w = len(pools0), len(where)
    tokens0 = mxnp.array(onp.zeros((1, cc), onp.int32))
    zero = mxnp.array(onp.zeros((), onp.int32))
    adapter = _ChunkStepAdapter(model)
    step_fn, params = adapter.functionalize(tokens0, *pools0, *where, zero,
                                            zero)
    tkey = (0.0, 0) if greedy else (float(temperature), int(top_k))
    ckey = ("state_prefill", cc, int(num_blocks), bool(greedy), *tkey,
            bool(donate), *ckey)
    store, cached = _decode_cache(model, ckey)
    if cached is not None:
        return cached, params

    def run(params, tokens, start, n_real, *pools_where_key):
        (logits, *counts_pools), _ = step_fn(
            params, tokens, *pools_where_key[:n + w], start, n_real)
        nxt = _sample(logits, pools_where_key[-1], greedy, temperature,
                      top_k)[0]
        return (_with_counts(nxt, counts_pools[:-n]), *counts_pools[-n:])

    jrun = _paged_jit(run, "llm.prefill_chunk",
                      tuple(range(4, 4 + n)) if donate else (), store)
    return jrun, params


def paged_suffix_prefill_program(model, *, suffix_len, num_blocks,
                                 block_size, max_blocks_per_seq,
                                 kv_cache_dtype=None, weight_dtype=None,
                                 greedy=True, temperature=1.0, top_k=0,
                                 donate=False):
    """Build (or fetch memoized) the shared-prefix *suffix* prefill
    program for one suffix-length bucket.

    When a prompt's leading full blocks are resident in the engine's
    prefix cache, only the uncached suffix needs compute. The suffix is
    fed as ONE multi-token paged step (``decode_step_paged`` with
    ``T = Sb``): every suffix token's K/V is written through the lane's
    block table at absolute positions ``start_pos + t``, and each token
    attends over the pool with length ``start_pos + t + 1`` — the
    cached prefix blocks feed the attention without ever being
    recomputed, and the per-position length mask IS the causal mask.

    Returns ``(run, params)``: ``run(params, suffix (1, Sb) i32,
    start_pos () i32, last_idx () i32, pool_k, pool_v, block_table
    (1, MB) i32, key) -> (first_token () i32, new_pool_k, new_pool_v)``.
    ``last_idx`` is the index WITHIN the suffix of the last real prompt
    token; pad tokens beyond it write length-masked garbage into
    lane-owned slots that real decode overwrites later."""
    cache_dtype = _resolve_cache_dtype(model, kv_cache_dtype)
    sb = int(suffix_len)
    bs = int(block_size)
    mb = int(max_blocks_per_seq)
    if sb % bs:
        raise MXNetError(
            f"suffix bucket {sb} must be a multiple of block_size {bs}")
    from ... import numpy as mxnp

    pk, pv = model.init_block_pool(min(int(num_blocks), 2), bs,
                                   dtype=cache_dtype)
    tokens0 = mxnp.array(onp.zeros((1, sb), onp.int32))
    bt0 = mxnp.array(onp.zeros((1, mb), onp.int32))
    pos0 = mxnp.array(onp.zeros((1,), onp.int32))
    adapter = _PagedStepAdapter(model)
    step_fn, params = adapter.functionalize(tokens0, pk, pv, bt0, pos0)
    step_fn, params = _apply_weight_dtype(model, step_fn, params,
                                          weight_dtype)
    tkey = (0.0, 0) if greedy else (float(temperature), int(top_k))
    ckey = ("paged_suffix", sb, int(num_blocks), bs, mb, bool(greedy),
            *tkey, cache_dtype, weight_dtype, bool(donate))
    store, cached = _decode_cache(model, ckey)
    if cached is not None:
        return cached, params

    def run(params, suffix, start_pos, last_idx, pool_k, pool_v, bt, key):
        pos = jnp.reshape(start_pos, (1,)).astype(jnp.int32)
        (logits, pool_k, pool_v), _ = step_fn(
            params, suffix, pool_k, pool_v, bt, pos)
        first = _sample(logits[:, last_idx], key, greedy, temperature,
                        top_k)[0]
        return first, pool_k, pool_v

    jrun = _paged_jit(run, "llm.prefill_suffix",
                      (4, 5) if donate else (), store)
    return jrun, params


# --- speculative decoding (draft-propose / verify-in-one-forward) ----------
def _policy_probs(logits, greedy, temperature, top_k):
    """The :func:`_sample` policy as explicit probabilities (..., V) —
    exact rejection sampling needs p and q, not just samples. Greedy is
    the argmax one-hot (so the verify math degenerates to exact token
    matching and spec decode stays token-identical)."""
    logits = logits.astype(jnp.float32)
    if greedy:
        best = jnp.argmax(logits, axis=-1)
        return jax.nn.one_hot(best, logits.shape[-1], dtype=jnp.float32)
    logits = logits / max(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.nn.softmax(logits, axis=-1)


def _spec_accept(target_logits, draft_logits, draft_toks, key, greedy,
                 temperature, top_k):
    """Exact rejection sampling over one verified draft window.

    ``target_logits``: (R, K+1, V) — the target forward over
    ``[last_token, d_0..d_{K-1}]``, so row ``i`` is the target's
    distribution for the token AFTER the first ``i`` draft tokens;
    ``draft_logits``: (R, K, V) the draft's proposal distributions;
    ``draft_toks``: (R, K). Returns ``(out_tokens (R, K+1), n_acc
    (R,))``: per lane, ``out[:n_acc]`` are the accepted draft tokens and
    ``out[n_acc]`` is the corrected/bonus token — so a verify step
    always emits ``n_acc + 1`` tokens.

    Greedy: accept while the draft matches the target argmax; the
    correction is the target argmax after the accepted prefix —
    emitted tokens are exactly the plain greedy stream. Sampled: accept
    ``d_i`` with prob ``min(1, p_i(d_i)/q_i(d_i))``; on first rejection
    sample from ``norm(max(p - q, 0))``; after K acceptances sample the
    bonus from ``p_K`` (the zero-padded q row makes that the same
    gather) — the emitted distribution equals plain sampling exactly
    (Leviathan et al.)."""
    r, kp1, v = target_logits.shape
    k = kp1 - 1
    if greedy:
        tgt = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
        match = (tgt[:, :k] == draft_toks).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1)
        n_acc = jnp.sum(acc, axis=1).astype(jnp.int32)
        correction = jnp.take_along_axis(tgt, n_acc[:, None], axis=1)
    else:
        p = _policy_probs(target_logits, greedy, temperature, top_k)
        q = _policy_probs(draft_logits, greedy, temperature, top_k)
        key, ku, kr = jax.random.split(key, 3)
        u = jax.random.uniform(ku, (r, k))
        p_d = jnp.take_along_axis(p[:, :k], draft_toks[:, :, None],
                                  axis=2)[..., 0]
        q_d = jnp.take_along_axis(q, draft_toks[:, :, None],
                                  axis=2)[..., 0]
        # u < p/q without the divide (q > 0 wherever the draft sampled)
        acc = jnp.cumprod((u * q_d < p_d).astype(jnp.int32), axis=1)
        n_acc = jnp.sum(acc, axis=1).astype(jnp.int32)
        # residual at the first rejection; a zero-padded q row turns the
        # all-accepted bonus draw into the same gather (residual = p_K)
        qz = jnp.concatenate([q, jnp.zeros((r, 1, v), q.dtype)], axis=1)
        sel = jnp.broadcast_to(n_acc[:, None, None], (r, 1, v))
        p_sel = jnp.take_along_axis(p, sel, axis=1)[:, 0]
        q_sel = jnp.take_along_axis(qz, sel, axis=1)[:, 0]
        resid = jnp.maximum(p_sel - q_sel, 0.0)
        tot = jnp.sum(resid, axis=-1, keepdims=True)
        # p == q exactly -> the residual underflows; any draw from p is
        # then distribution-correct
        resid = jnp.where(tot > 1e-20, resid / jnp.maximum(tot, 1e-20),
                          p_sel)
        correction = jax.random.categorical(
            kr, jnp.log(jnp.maximum(resid, 1e-30)),
            axis=-1).astype(jnp.int32)[:, None]
    cols = jnp.arange(kp1, dtype=jnp.int32)[None]
    padded = jnp.concatenate(
        [draft_toks.astype(jnp.int32), jnp.zeros((r, 1), jnp.int32)],
        axis=1)
    out = jnp.where(cols < n_acc[:, None], padded,
                    jnp.broadcast_to(correction, (r, kp1)))
    return out.astype(jnp.int32), n_acc


def paged_spec_draft_program(model, *, max_running, draft_k, num_blocks,
                             block_size, max_blocks_per_seq,
                             kv_cache_dtype=None, weight_dtype=None,
                             greedy=True, temperature=1.0, top_k=0,
                             donate=False):
    """Build (or fetch memoized) the draft-proposal program: K
    sequential single-token steps of the (small) draft model inside ONE
    compiled program.

    Returns ``(run, params)``: ``run(params, prev_tok (R,1), last_tok
    (R,1), pool_k, pool_v, block_table (R,MB), positions (R,), key) ->
    (draft_toks (R,K) i32, draft_logits (R,K,V) f32, new_pool_k,
    new_pool_v)``. ``positions[r]`` is the write position of
    ``last_tok`` (= the lane's current length); ``prev_tok`` (the token
    at ``positions-1``) is re-forwarded first to heal the one-position
    draft-cache gap a fully-accepted round leaves — idempotent when the
    position is already resident. Draft-pool content only ever affects
    ACCEPTANCE RATE, never output correctness: every proposal is
    verified exactly by the target."""
    cache_dtype = _resolve_cache_dtype(model, kv_cache_dtype)
    r, mb, kk = int(max_running), int(max_blocks_per_seq), int(draft_k)
    if kk < 1:
        raise MXNetError(f"draft_k must be >= 1, got {kk}")
    from ... import numpy as mxnp

    pk, pv = model.init_block_pool(min(int(num_blocks), 2), block_size,
                                   dtype=cache_dtype)
    tokens0 = mxnp.array(onp.zeros((r, 1), onp.int32))
    bt0 = mxnp.array(onp.zeros((r, mb), onp.int32))
    pos0 = mxnp.array(onp.zeros((r,), onp.int32))
    adapter = _PagedStepAdapter(model)
    step_fn, params = adapter.functionalize(tokens0, pk, pv, bt0, pos0)
    step_fn, params = _apply_weight_dtype(model, step_fn, params,
                                          weight_dtype)
    tkey = (0.0, 0) if greedy else (float(temperature), int(top_k))
    ckey = ("spec_draft", r, kk, int(num_blocks), int(block_size), mb,
            bool(greedy), *tkey, cache_dtype, weight_dtype, bool(donate))
    store, cached = _decode_cache(model, ckey)
    if cached is not None:
        return cached, params

    def run(params, prev_tok, last_tok, pool_k, pool_v, bt, pos, key):
        pos = pos.astype(jnp.int32)
        (_, pool_k, pool_v), _ = step_fn(
            params, prev_tok, pool_k, pool_v, bt,
            jnp.maximum(pos - 1, 0))
        tok = last_tok
        toks, lgs = [], []
        for i in range(kk):
            (lg, pool_k, pool_v), _ = step_fn(
                params, tok, pool_k, pool_v, bt, pos + i)
            lg = lg[:, -1].astype(jnp.float32)
            key, sub = jax.random.split(key)
            nxt = _sample(lg, sub, greedy, temperature, top_k)
            toks.append(nxt)
            lgs.append(lg)
            tok = nxt[:, None]
        return (jnp.stack(toks, axis=1), jnp.stack(lgs, axis=1),
                pool_k, pool_v)

    jrun = _paged_jit(run, "llm.draft", (3, 4) if donate else (), store)
    return jrun, params


def paged_spec_verify_program(model, *, max_running, draft_k, num_blocks,
                              block_size, max_blocks_per_seq,
                              kv_cache_dtype=None, weight_dtype=None,
                              greedy=True, temperature=1.0, top_k=0,
                              donate=False):
    """Build (or fetch memoized) the verify program: the TARGET model
    scores ``[last_token, d_0..d_{K-1}]`` in ONE batched (R, K+1)
    forward through the paged pool (amortizing the whole layer stack's
    launches over K+1 tokens), then runs :func:`_spec_accept`.

    Returns ``(run, params)``: ``run(params, last_tok (R,1), draft_toks
    (R,K), draft_logits (R,K,V), pool_k, pool_v, block_table (R,MB),
    positions (R,), key) -> (out_toks (R,K+1), n_acc (R,), new_pool_k,
    new_pool_v)``. The forward writes K+1 KV rows per lane at
    ``positions + [0..K]``; rows past the accepted prefix are
    length-masked garbage the next round overwrites — rollback is just
    not advancing ``positions``."""
    cache_dtype = _resolve_cache_dtype(model, kv_cache_dtype)
    r, mb, kk = int(max_running), int(max_blocks_per_seq), int(draft_k)
    if kk < 1:
        raise MXNetError(f"draft_k must be >= 1, got {kk}")
    from ... import numpy as mxnp

    pk, pv = model.init_block_pool(min(int(num_blocks), 2), block_size,
                                   dtype=cache_dtype)
    tokens0 = mxnp.array(onp.zeros((r, kk + 1), onp.int32))
    bt0 = mxnp.array(onp.zeros((r, mb), onp.int32))
    pos0 = mxnp.array(onp.zeros((r,), onp.int32))
    adapter = _PagedStepAdapter(model)
    step_fn, params = adapter.functionalize(tokens0, pk, pv, bt0, pos0)
    step_fn, params = _apply_weight_dtype(model, step_fn, params,
                                          weight_dtype)
    tkey = (0.0, 0) if greedy else (float(temperature), int(top_k))
    ckey = ("spec_verify", r, kk, int(num_blocks), int(block_size), mb,
            bool(greedy), *tkey, cache_dtype, weight_dtype, bool(donate))
    store, cached = _decode_cache(model, ckey)
    if cached is not None:
        return cached, params

    def run(params, last_tok, draft_toks, draft_logits, pool_k, pool_v,
            bt, pos, key):
        tokens = jnp.concatenate(
            [last_tok.astype(jnp.int32), draft_toks.astype(jnp.int32)],
            axis=1)
        (logits, pool_k, pool_v), _ = step_fn(
            params, tokens, pool_k, pool_v, bt, pos.astype(jnp.int32))
        out, n_acc = _spec_accept(
            logits.astype(jnp.float32), draft_logits,
            draft_toks.astype(jnp.int32), key, greedy, temperature,
            top_k)
        return out, n_acc, pool_k, pool_v

    jrun = _paged_jit(run, "llm.verify", (4, 5) if donate else (), store)
    return jrun, params
