"""Serving engines over the folded integer model.

``Engine`` — true continuous batching around a single token-budget step
loop: a fixed slot table shares one compiled decode graph; every slot
carries its own position (per-slot ``pos`` vector into ``serve_forward``),
requests are admitted mid-flight into free slots and evicted on
EOS/max-tokens by the ``Scheduler``.  Prefill is no longer a monolithic
one-shot forward at admission: each tick the scheduler carves waiting and
partially-prefilled prompts into page-aligned chunks under a shared token
budget (``max_batched_tokens`` per tick, ``max_prefill_chunk`` per slot)
and interleaves them with the decode batch, so a very long prompt can no
longer stall every decoding slot for the duration of its prefill.  A slot
keeps a ``prefill_pos`` cursor; its final chunk's last-row logits hand the
request into decode without an extra forward.  With both knobs unset a
prompt still prefills in one chunk — the pre-chunking behavior, now just a
degenerate schedule of the same loop.

Chunk forwards run through the decode-identical row datapath on the
ref/interpret kernel backends (CPU serving and CI), so a request's greedy
tokens are bit-for-bit what the lockstep engine produces for it alone —
and bit-for-bit identical across chunk sizes: chunking changes latency,
not outputs.  On the compiled pallas backend both prefill chunks and
decode dispatch to the q7 flash family instead (chunks go through the
block-table-walking ``paged_prefill_qattention`` kernel; self-consistent
integer datapath, but not bit-identical to the jnp path).  SSM/hybrid
architectures (whose prefill is a recurrence) fall back to a batch-1
decode-loop prefill, run as a single chunk of the same loop.

Cache layouts (``cache_layout=``):

* ``"paged"`` — the int8 KV cache is a global pool of fixed-size pages; each
  slot carries a block-table row instead of an exclusive ``Smax`` stripe.
  By default (``reserve_policy="ondemand"``) admission reserves only the
  PROMPT's pages; decode slots request their next page when the write
  cursor crosses a page boundary, and when the pool runs dry the engine
  preempts a victim — spill registers its finished pages in the prefix
  registry and requeues it at the queue front; restore replays through the
  ordinary chunk-continuation path, hitting the registry for whatever
  survived.  ``reserve_policy="full"`` keeps the PR-2 contract (prompt +
  decode budget reserved up front, decode can never OOM, overload stalls
  admission) for latency-critical serving where recompute is unacceptable.
  Prompt prefixes are shared at page granularity through the allocator's
  refcounted registry: a repeated system prompt maps cached pages and only
  the unseen suffix runs through the model.  Chunked prefill requires this
  layout (chunks are pages).
* ``"contiguous"`` — the original dense ``(B, Smax, Hkv, hd)`` stripe per
  slot (kept for one release as the A/B baseline; SWA ring buffers and
  SSM/hybrid archs always use it).  Prefill is always one whole-prompt
  chunk.
* ``"auto"`` (default) — paged when the arch supports it (all-attention,
  no sliding window), else contiguous.

Tensor parallelism (``tp=N`` or an explicit ``mesh``, paged layout only):
the page pool shards over its KV-head axis — every rank holds its heads'
slice of EVERY page, so page ids are global, block tables replicate, and
the host-side allocator/scheduler stays a single authority whose
admission/grow/preempt/spill decisions bind all ranks at once
(spill/restore never moves data across ranks; registration and replay are
rank-local).  Decode and prefill-chunk forwards run under one shard_map:
heads split per rank, chunks are the cross-rank work-division unit for
prefill, and contexts all-gather before the output projection, so sharded
greedy outputs are bit-identical to the unsharded engine on the
ref/interpret backends.  On CPU, simulate ranks with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the test-tp CI
lane's recipe).

Event-driven serving API (the surface the asyncio frontend, the replica
router, and the bench all drive): ``submit()`` / ``poll()`` / ``cancel()``.
``poll()`` runs ONE scheduler tick — admit → chunk-prefill-under-budget →
decode, exactly the loop described above — and returns the tick's
``TokenEvent`` stream: one event per emitted token plus terminal events
for cancelled and deadline-shed requests.  ``step()``, ``run()`` and
``generate()`` are thin wrappers over ``poll()``, so a bench run and a
server run cannot diverge in behavior — they are the same code path.
Requests carry an explicit lifecycle (``RequestStatus``: WAITING →
PREFILL → DECODE → FINISHED, with CANCELLED and FAILED exits) and a
``result()`` accessor; ``cancel()`` flows through this state machine and
frees a seated request's pages via the ordinary eviction path.
Construction takes a typed ``EngineConfig`` (the PR-6 legacy ``**kwargs``
surface is gone; keyword options raise a TypeError naming the fix).

Speculative decoding (``EngineConfig(spec_k=K)``, paged int8 layout):
each tick a pluggable :class:`~repro.serve.draft.DraftSource` proposes up
to K tokens per greedy decode slot; ONE verify forward (``mode="verify"``
— the chunk-prefill datapath at per-slot ragged positions) scores every
slot's ``[last_token, drafts...]`` rows at once, and the engine accepts
the longest prefix whose drafts match the argmax chain plus one bonus
token.  Accepted rows' K/V are already committed through the block table
(pages grown up front via ``Scheduler.grow``); a rejected tail just
leaves the write cursor behind the garbage rows, which the causal length
masks hide until the owner rewrites them — allocator state never moves.
Because acceptance is exact argmax matching, speculative greedy outputs
are bit-identical to plain decode (``spec_k=0``) on the row-exact
backends; the counters ``drafted`` / ``accepted`` / ``rejected`` /
``accept_len_hist`` report the win rate.

``LockstepEngine`` — the original batch demo (kept as the benchmark baseline
and for SSM/audio archs): lockstep decoding with one shared position scalar,
prefill replayed token-by-token for the whole batch, admission only between
``generate()`` calls.
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import math
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import serve_int as S
from repro.models.transformer import slot_kinds
from repro.serve import stats as stats_schema
from repro.serve.scheduler import (BlockAllocator, Scheduler, SlotState,
                                   pages_needed)

# host spans on the profiler's clock; every name is a key of stats.SPANS
_span = jax.profiler.TraceAnnotation


class RequestStatus(enum.Enum):
    """Explicit request lifecycle.  WAITING → PREFILL → DECODE → FINISHED
    is the happy path; preemption moves a seated request back to WAITING;
    CANCELLED (explicit cancel or deadline shed) and FAILED are terminal
    exits.  Callers read ``Request.status`` / ``Request.result()`` instead
    of peeking at engine internals."""
    WAITING = "waiting"
    PREFILL = "prefill"
    DECODE = "decode"
    FINISHED = "finished"
    CANCELLED = "cancelled"
    FAILED = "failed"

    @property
    def terminal(self) -> bool:
        return self in (RequestStatus.FINISHED, RequestStatus.CANCELLED,
                        RequestStatus.FAILED)


class RequestCancelled(RuntimeError):
    """Raised by ``Request.result()`` for a cancelled / shed request."""


class RequestFailed(RuntimeError):
    """Raised by ``Request.result()`` for a failed request."""


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One element of the ``poll()`` event stream.

    ``token`` is None for terminal events that do not carry a token
    (cancellation, deadline shed, failure); ``index`` is the token's
    0-based position in the request's output stream (for a terminal
    non-token event: the number of tokens emitted before it).  ``final``
    marks the request's last event — its status is terminal from here and
    ``finish_reason`` says why: ``length`` / ``eos`` (FINISHED),
    ``cancelled`` / ``deadline`` (CANCELLED), ``error`` (FAILED)."""
    rid: int
    token: Optional[int]
    index: int
    final: bool
    finish_reason: Optional[str] = None


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    eos_token: Optional[int] = None
    deadline_tick: Optional[int] = None  # shed if still WAITING at this tick
    out: Optional[np.ndarray] = None
    # --- lifecycle (owned by the engine/router after submit) -------------
    rid: Optional[int] = None
    status: RequestStatus = RequestStatus.WAITING
    finish_reason: Optional[str] = None

    def result(self) -> np.ndarray:
        """The generated tokens once FINISHED.  Raises ``RequestCancelled``
        / ``RequestFailed`` on the terminal exits (``out`` still holds the
        partial tokens emitted before the exit) and ``RuntimeError`` while
        the request is in flight."""
        if self.status is RequestStatus.FINISHED:
            return self.out
        if self.status is RequestStatus.CANCELLED:
            raise RequestCancelled(
                f"request rid={self.rid} cancelled ({self.finish_reason}); "
                f"{0 if self.out is None else len(self.out)} partial "
                f"token(s) in .out")
        if self.status is RequestStatus.FAILED:
            raise RequestFailed(
                f"request rid={self.rid} failed ({self.finish_reason})")
        raise RuntimeError(
            f"request rid={self.rid} still in flight "
            f"(status={self.status.value})")


class EngineConfigError(ValueError):
    """An EngineConfig is invalid or incompatible with the model config."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Typed, validated engine construction options (replaces the old
    opaque ``**kwargs``).  Field-level constraints are checked by
    ``validate()`` at engine construction; model-dependent compatibility
    (paged layout support, TP divisibility) is checked by the engine with
    the same ``EngineConfigError``.  Unknown options raise ``TypeError``
    naming the valid fields (``from_kwargs``)."""
    batch_slots: int = 8
    max_len: int = 512
    seed: int = 0
    prefill_bucket: int = 16
    cache_layout: str = "auto"           # auto | paged | contiguous
    page_size: int = 16
    n_pages: Optional[int] = None
    max_batched_tokens: Optional[int] = None
    max_prefill_chunk: Optional[int] = None
    reserve_policy: Optional[str] = None  # None | "full" | "ondemand"
    kv_bits: int = 8                      # 8 (identity default) | 4 (packed)
    tp: int = 1
    mesh: object = None
    spec_k: int = 0                       # max draft tokens/slot/tick (0=off)
    draft: object = "prompt_lookup"       # DraftSource instance or name
    device: object = None                 # jax Device for params + pool
                                          # (None: JAX's default device)

    @classmethod
    def from_kwargs(cls, **kw) -> "EngineConfig":
        """Build from keyword options; unknown names raise a TypeError
        listing the valid fields (the old ``**kw`` surface silently
        warned or dropped — now it is an error)."""
        valid = [f.name for f in dataclasses.fields(cls)]
        unknown = sorted(set(kw) - set(valid))
        if unknown:
            raise TypeError(
                f"unknown engine option(s) {', '.join(unknown)}; valid "
                f"EngineConfig fields: {', '.join(valid)}")
        return cls(**kw)

    def validate(self) -> "EngineConfig":
        """Field-level validation (model-independent); raises
        ``EngineConfigError`` with an actionable message."""
        def bad(msg):
            raise EngineConfigError(f"invalid EngineConfig: {msg}")
        if self.batch_slots < 1:
            bad(f"batch_slots must be >= 1 (got {self.batch_slots})")
        if self.max_len < 1:
            bad(f"max_len must be >= 1 (got {self.max_len})")
        if self.prefill_bucket < 1:
            bad(f"prefill_bucket must be >= 1 (got {self.prefill_bucket})")
        if self.cache_layout not in ("auto", "paged", "contiguous"):
            bad(f"cache_layout must be auto|paged|contiguous "
                f"(got {self.cache_layout!r})")
        if self.page_size < 1:
            bad(f"page_size must be >= 1 (got {self.page_size})")
        if self.n_pages is not None and self.n_pages < 2:
            bad(f"n_pages must be >= 2 — page 0 is the reserved trash page "
                f"(got {self.n_pages})")
        if self.reserve_policy not in (None, "full", "ondemand"):
            bad(f"reserve_policy must be full|ondemand "
                f"(got {self.reserve_policy!r})")
        chunky = self.max_batched_tokens is not None or \
            self.max_prefill_chunk is not None
        if chunky and self.cache_layout == "contiguous":
            bad("chunked prefill (max_batched_tokens / max_prefill_chunk) "
                "requires cache_layout='paged' — chunks are pages")
        if self.max_batched_tokens is not None and self.max_batched_tokens < 1:
            bad(f"max_batched_tokens must be >= 1 "
                f"(got {self.max_batched_tokens})")
        if self.max_prefill_chunk is not None and (
                self.max_prefill_chunk < self.page_size
                or self.max_prefill_chunk % self.page_size):
            bad(f"max_prefill_chunk must be a positive multiple of "
                f"page_size={self.page_size} (got {self.max_prefill_chunk})")
        if self.reserve_policy == "ondemand" and \
                self.cache_layout == "contiguous":
            bad("reserve_policy='ondemand' (on-demand page growth) requires "
                "cache_layout='paged'")
        if self.kv_bits not in (8, 4):
            bad(f"kv_bits must be one of 8, 4 (got {self.kv_bits})")
        if self.kv_bits != 8 and self.cache_layout == "contiguous":
            bad("kv_bits=4 packs the paged KV pool; "
                "cache_layout='contiguous' stores int8 rows only")
        if self.tp < 1:
            bad(f"tp must be >= 1 (got {self.tp})")
        if (self.tp != 1 or self.mesh is not None) and \
                self.cache_layout == "contiguous":
            bad("tensor parallelism shards the paged KV pool; "
                "cache_layout='contiguous' has no TP path")
        if self.device is not None and (self.tp != 1
                                        or self.mesh is not None):
            bad("device places a single-rank engine; a tensor-parallel "
                "engine's devices come from its mesh")
        if self.spec_k < 0:
            bad(f"spec_k must be >= 0 (got {self.spec_k})")
        if self.spec_k > 0 and self.cache_layout == "contiguous":
            bad("speculative decoding (spec_k > 0) verifies through the "
                "paged prefill path; cache_layout='contiguous' has no "
                "verify forward")
        if self.spec_k > 0 and self.kv_bits != 8:
            bad("spec_k > 0 with kv_bits=4 is not supported: a verify "
                "forward's multi-row write + rollback would re-derive "
                "page scales decode already froze (spec x kv4 interaction "
                "is a tracked ROADMAP follow-up)")
        return self


_DEFAULT_CONFIG = EngineConfig()
# fields the LockstepEngine has no use for; make_engine warns when they
# deviate from their defaults and resets them before construction
_CONTINUOUS_ONLY_FIELDS = ("prefill_bucket", "cache_layout", "page_size",
                           "n_pages", "max_batched_tokens",
                           "max_prefill_chunk", "reserve_policy", "kv_bits",
                           "tp", "mesh", "spec_k", "draft", "device")


def _config_only(config: Optional[EngineConfig], kw: dict,
                 caller: str) -> EngineConfig:
    """Engines construct from an EngineConfig ONLY.  The PR-6 one-release
    ``**kwargs`` DeprecationWarning shim is gone; the old keyword surface
    now fails fast with a TypeError that names the replacement instead of
    python's generic unexpected-keyword message."""
    if kw:
        raise TypeError(
            f"{caller}(cfg, folded, {next(iter(kw))}=..., ...) keyword "
            f"options were removed (deprecated one release ago); pass "
            f"{caller}(cfg, folded, EngineConfig(...)) — valid fields: "
            f"{', '.join(f.name for f in dataclasses.fields(EngineConfig))}")
    return (config if config is not None else EngineConfig()).validate()


def supports_continuous(cfg: ModelConfig) -> bool:
    """Continuous batching serves single-head token-LM archs; codebook/audio
    and multi-head archs go through LockstepEngine (see make_engine)."""
    return cfg.frontend == "none" and cfg.n_lm_heads == 1


def make_engine(cfg: ModelConfig, folded,
                config: Optional[EngineConfig] = None, **kw):
    """The continuous engine when the arch supports it, else the lockstep
    baseline (same generate() surface).  Continuous-only EngineConfig
    fields set to non-default values for a lockstep arch are reset with a
    warning — not silently."""
    config = _config_only(config, kw, "make_engine")
    if supports_continuous(cfg):
        return Engine(cfg, folded, config)
    dropped = sorted(f for f in _CONTINUOUS_ONLY_FIELDS
                     if getattr(config, f) != getattr(_DEFAULT_CONFIG, f))
    if dropped:
        warnings.warn(
            f"make_engine: arch {cfg.name!r} takes the LockstepEngine, "
            f"which ignores {', '.join(dropped)}", stacklevel=2)
        config = dataclasses.replace(
            config, **{f: getattr(_DEFAULT_CONFIG, f) for f in dropped})
    return LockstepEngine(cfg, folded, config)


class Engine:
    """Continuous-batching integer serving engine (token-budget step loop)."""

    def __init__(self, cfg: ModelConfig, folded,
                 config: Optional[EngineConfig] = None, **kw):
        config = _config_only(config, kw, "Engine")
        if not supports_continuous(cfg):
            raise EngineConfigError(
                f"continuous engine serves token-LM archs; arch "
                f"{cfg.name!r} needs LockstepEngine (use make_engine)")
        self.cfg = cfg
        # one-chip replicas each live on their own device: params here, the
        # pool in _init_state; jitted steps then run where their inputs are
        self.device = config.device
        self.folded = (folded if self.device is None
                       else jax.device_put(folded, self.device))
        self.config = config
        batch_slots, max_len = config.batch_slots, config.max_len
        cache_layout, page_size = config.cache_layout, config.page_size
        tp, mesh = config.tp, config.mesh
        self.batch = batch_slots
        self.max_len = max_len
        self.smax = S.cache_rows(cfg, max_len)
        self.prefill_bucket = config.prefill_bucket
        # one-shot prefill needs every mixer to be cache-writing attention
        self._attn_only = cfg.causal and \
            all(m == "attn" for m, _ in slot_kinds(cfg))
        # the paged pool ignores the ACTIVATION-constraint mesh context
        # (that ctx drives the contiguous layout's SPMD constrain guards):
        # under an active ctx auto falls back to contiguous and an explicit
        # "paged" is refused rather than silently slow.  Tensor parallelism
        # for the paged pool goes through the engine-level ``tp``/``mesh``
        # config fields instead (shard_map over the pool's Hkv axis, below).
        from repro.sharding import partition as Pt
        pageable = self._attn_only and not cfg.sliding_window \
            and Pt.get_mesh_ctx() is None
        if cache_layout == "auto":
            cache_layout = "paged" if pageable else "contiguous"
        if cache_layout == "paged" and not pageable:
            raise EngineConfigError(
                "cache_layout='paged' requires an all-attention, non-SWA "
                "arch and no active device mesh; use cache_layout='auto' "
                "to fall back to contiguous")
        self.layout = cache_layout
        self.page_size = page_size
        if cache_layout != "paged" and (
                config.max_batched_tokens is not None
                or config.max_prefill_chunk is not None):
            raise EngineConfigError(
                "chunked prefill (max_batched_tokens / max_prefill_chunk) "
                "requires the paged cache layout, but cache_layout resolved "
                f"to {cache_layout!r} for arch {cfg.name!r}")
        self.max_batched_tokens = config.max_batched_tokens
        self.max_prefill_chunk = config.max_prefill_chunk
        # page-reservation policy: on-demand growth + preemption is the
        # default for the paged pool (the memory win paging exists for);
        # "full" restores the reserve-everything-at-admission contract
        if self.layout == "paged":
            self.reserve_policy = config.reserve_policy or "ondemand"
        else:
            if config.reserve_policy == "ondemand":
                raise EngineConfigError(
                    "reserve_policy='ondemand' requires the paged cache "
                    "layout, but cache_layout resolved to "
                    f"{cache_layout!r} for arch {cfg.name!r}")
            self.reserve_policy = "full"
        # KV pool precision: 8 is the identity-contract default; 4 packs
        # pages to nibbles (paged layout only — validate() already rejects
        # an EXPLICIT contiguous+kv4 combination, this handles 'auto'
        # resolving to contiguous for archs the paged pool can't serve)
        self.kv_bits = config.kv_bits
        if self.kv_bits != 8 and self.layout != "paged":
            warnings.warn(
                f"kv_bits={self.kv_bits} requires the paged cache layout, "
                f"but cache_layout resolved to {self.layout!r} for arch "
                f"{cfg.name!r}; falling back to kv_bits=8", stacklevel=2)
            self.kv_bits = 8
        # speculative decoding: validate() rejects explicit bad combos;
        # this guards 'auto' resolving to a layout the verifier can't serve
        self.spec_k = config.spec_k
        self.draft = None
        if self.spec_k:
            if self.layout != "paged" or self.kv_bits != 8:
                raise EngineConfigError(
                    f"speculative decoding (spec_k={self.spec_k}) requires "
                    f"the int8 paged cache layout, but arch {cfg.name!r} "
                    f"resolved to layout={self.layout!r} "
                    f"kv_bits={self.kv_bits}")
            from repro.serve.draft import make_draft_source
            self.draft = make_draft_source(config.draft)
        if self.layout == "paged":
            self.max_blocks = pages_needed(self.smax, page_size)
            # +1: page 0 is the reserved trash page (inactive-slot writes)
            self.n_pages = config.n_pages if config.n_pages is not None \
                else batch_slots * self.max_blocks + 1
            assert self.n_pages >= 2
        # --- tensor parallelism (paged pool sharded over KV heads) -------
        # Every rank holds its heads' slice of EVERY page: page ids stay
        # global, the block tables replicated, and the host-side
        # allocator/scheduler a single authority whose grow/preempt/spill
        # decisions apply to all ranks' slices at once.  tp=1 with an
        # explicit 1-device mesh runs the same shard_map path degenerately
        # (the no-simulation CI fallback).
        if mesh is None and tp != 1:
            from repro.launch.mesh import make_tp_mesh
            mesh = make_tp_mesh(tp)
        self.mesh = mesh
        if mesh is not None:
            if self.layout != "paged":
                raise EngineConfigError(
                    "tensor parallelism shards the paged KV pool; the "
                    "contiguous layout has no TP path")
            assert "model" in mesh.axis_names, mesh.axis_names
            self.tp = int(mesh.shape["model"])
            assert tp in (1, self.tp), (tp, self.tp)
            if cfg.n_kv_heads % self.tp:
                raise EngineConfigError(
                    f"TP={self.tp} must divide n_kv_heads={cfg.n_kv_heads} "
                    "(each rank owns a whole slice of KV heads)")
            # params replicate on every rank once, not per forward call
            from jax.sharding import NamedSharding, PartitionSpec
            self.folded = jax.device_put(
                self.folded, NamedSharding(mesh, PartitionSpec()))
        else:
            self.tp = 1
        # cross-replica shared prefix tier (attach_prefix_tier): survives
        # reset() — attachment is construction-level wiring, like the mesh
        self.prefix_tier = None
        self._init_state(config.seed)

        if self.layout == "paged":
            tp_axis = "model" if self.mesh is not None else None

            def decode_step(folded_, cache, tok, pos, btab):
                return S.serve_forward(cfg, folded_, tok, cache=cache,
                                       pos_offset=pos, mode="decode",
                                       block_tables=btab, tp_axis=tp_axis)

            def prefill(folded_, cache, toks, btab, pos0):
                return S.serve_forward(cfg, folded_, toks, cache=cache,
                                       pos_offset=pos0, mode="prefill",
                                       block_tables=btab, tp_axis=tp_axis)

            def verify(folded_, cache, toks, pos, btab, nrows):
                return S.serve_forward(cfg, folded_, toks, cache=cache,
                                       pos_offset=pos, mode="verify",
                                       block_tables=btab, verify_rows=nrows,
                                       tp_axis=tp_axis)

            if self.mesh is not None:
                # one shard_map around the whole forward: the pool enters
                # as the rank-local Hkv slice; tokens, positions, and the
                # block table replicate; logits come back replicated (the
                # forward all-gathers heads before the output projection)
                from jax.sharding import PartitionSpec as P
                from repro.sharding import partition as Pt
                # per-leaf specs: kv4 pools carry 2-D (n_reps, n_pages)
                # scale leaves next to the 5-D packed payloads, so one
                # broadcast pspec would rank-mismatch — match each leaf
                # (the forward promises replicated outputs itself through
                # its all_gathers, so the replication check stays off)
                pool, rep = Pt.kv_pool_specs(self.cache, self.mesh), P()
                decode_step = jax.shard_map(
                    decode_step, mesh=self.mesh,
                    in_specs=(rep, pool, rep, rep, rep),
                    out_specs=(rep, pool), check_vma=False)
                prefill = jax.shard_map(
                    prefill, mesh=self.mesh,
                    in_specs=(rep, pool, rep, rep, rep),
                    out_specs=(rep, pool), check_vma=False)
                verify = jax.shard_map(
                    verify, mesh=self.mesh,
                    in_specs=(rep, pool, rep, rep, rep, rep),
                    out_specs=(rep, pool), check_vma=False)
            self._decode = jax.jit(decode_step, donate_argnums=(1,))
            # the chunk forward: writes straight through the block table
            # into the (donated) pool at page-aligned ``pos0`` and attends
            # over the slot's whole mapped chain; one compiled shape per
            # chunk size (retraces per distinct padded length)
            self._prefill = jax.jit(prefill, donate_argnums=(1,))
            # the speculative verify forward: (B, spec_k+1) tokens at
            # per-slot ragged positions; one compiled shape total (ragged
            # proposal lengths pad to spec_k+1, verify_rows masks the rest)
            self._verify = jax.jit(verify, donate_argnums=(1,))
        else:
            def decode_step(folded_, cache, tok, pos):
                return S.serve_forward(cfg, folded_, tok, cache=cache,
                                       pos_offset=pos, mode="decode")

            # one graph for the slot table AND (by retrace) the batch-1
            # prefill loop
            self._decode = jax.jit(decode_step, donate_argnums=(1,))

            def prefill(folded_, toks):
                cache1 = S.init_cache(cfg, 1, max_len)
                return S.serve_forward(cfg, folded_, toks, cache=cache1,
                                       mode="prefill")

            self._prefill = jax.jit(prefill)  # retraces per bucketed length

            def write_slot(cache, cache1, b):
                def put(c, c1):
                    starts = (0, b) + (0,) * (c.ndim - 2)
                    return jax.lax.dynamic_update_slice(c, c1, starts)
                return jax.tree.map(put, cache, cache1)

            self._write_slot = jax.jit(write_slot, donate_argnums=(0,))

    @staticmethod
    def _zero_counters() -> Dict[str, int]:
        # built FROM the frozen schema: adding a counter means adding it to
        # repro.serve.stats.COUNTERS (with a description) first — the dict
        # and the schema cannot drift apart
        c: Dict = {k: 0 for k in stats_schema.COUNTERS}
        c["accept_len_hist"] = {}    # the one non-scalar: {accept_len: n}
        return c

    def _init_state(self, seed: int):
        self.requests: Dict[int, Request] = {}
        # terminal events produced between polls (cancel, deadline shed);
        # drained at the head of the next poll()
        self._events: List[TokenEvent] = []
        self.pos = np.zeros(self.batch, np.int32)
        self.rng = np.random.default_rng(seed)
        self.counters = self._zero_counters()
        if self.layout == "paged":
            self.alloc = BlockAllocator(
                self.n_pages, self.page_size,
                bytes_per_page=S.paged_page_nbytes(self.cfg, self.page_size,
                                                   self.kv_bits))
            self.sched = Scheduler(self.batch, allocator=self.alloc,
                                   max_batched_tokens=self.max_batched_tokens,
                                   max_prefill_chunk=self.max_prefill_chunk,
                                   reserve=self.reserve_policy)
            with self._on_device():
                self.cache = S.init_paged_cache(self.cfg, self.n_pages,
                                                self.page_size, self.kv_bits)
            if self.mesh is not None:
                # lay the pool out sharded before the first donated step so
                # every forward reuses the same per-rank Hkv-slice buffers
                from repro.sharding import partition as Pt
                self.cache = jax.device_put(
                    self.cache, Pt.paged_pool_shardings(self.mesh, self.cache))
            self.block_tables = np.zeros((self.batch, self.max_blocks),
                                         np.int32)
        else:
            self.alloc = None
            self.sched = Scheduler(self.batch)
            with self._on_device():
                self.cache = S.init_cache(self.cfg, self.batch, self.max_len)

    def _on_device(self):
        return (contextlib.nullcontext() if self.device is None
                else jax.default_device(self.device))

    def reset(self, seed: int = 0):
        """Clear all serving state; keeps the compiled graphs."""
        self._init_state(seed)

    # --- cross-replica prefix sharing ------------------------------------

    @property
    def prefix_store(self):
        """The engine's local :class:`~repro.serve.prefix.PrefixStore`
        (the allocator-owned registry), or None for the contiguous
        layout.  Read-only consumers — the router's affinity probe, the
        adoption path — program against this; reference-counted access
        stays behind ``BlockAllocator.match_prefix``."""
        return self.alloc.prefix if self.layout == "paged" else None

    def attach_prefix_tier(self, tier):
        """Wire a :class:`~repro.serve.prefix.SharedPrefixTier` into this
        engine: prefill handoffs publish their sealed chains, and waiting
        prompts adopt matching chains before admission (installed through
        the restore path: payload bytes land in freshly allocated pages
        that are registered and parked, so the subsequent admission sees
        an ordinary prefix hit).  Requires the paged int pool on a single
        rank — under TP each rank holds only its Hkv slice of a page, so
        publish/adopt needs per-rank payload slices (a tracked ROADMAP
        follow-up)."""
        if self.layout != "paged":
            raise EngineConfigError(
                "a shared prefix tier needs the paged cache layout; this "
                f"engine resolved to {self.layout!r}")
        if self.mesh is not None:
            raise EngineConfigError(
                "shared prefix tier under TP needs per-rank publish "
                "slices (ROADMAP follow-up); detach TP or the tier")
        if tier.page_size != self.page_size:
            raise EngineConfigError(
                f"tier page_size={tier.page_size} != engine "
                f"page_size={self.page_size}")
        self.prefix_tier = tier

    def _pool_leaves(self):
        """The paged pool as ``[(leaf_name, array)]`` with a stable
        path-derived name per leaf — the key space SealedChain payloads
        use.  Every leaf (int8/int4 payload and kv4 per-page scales) has
        the pool page axis at axis 1, so page gather/scatter is uniform."""
        flat, _ = jax.tree_util.tree_flatten_with_path(self.cache)
        return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]

    def _publish_prefix(self, prompt: List[int]):
        """Export the just-registered chain's pages the tier doesn't hold
        yet (device->host gather per cache leaf)."""
        from repro.serve.prefix import SealedChain
        tier = self.prefix_tier
        chain = self.alloc.prefix.seal(
            prompt, (len(prompt) - 1) // self.page_size)
        if chain.n_pages == 0:
            return
        held = tier.match(chain.tokens(), chain.n_pages).n_pages
        if held >= chain.n_pages:
            return
        idx = np.asarray(chain.pages[held:], np.int32)
        payload = {name: np.asarray(leaf[:, idx])
                   for name, leaf in self._pool_leaves()}
        sealed = SealedChain(self.page_size, chain.keys[held:],
                             chain.segs[held:], payload)
        self.counters["published_pages"] += tier.publish(sealed)

    def _install_pages(self, sealed, pages: List[int]):
        """Scatter a sealed chain's payload bytes into this pool at
        ``pages`` (host->device, one ``.at[].set`` per leaf).  The bytes
        are exact copies of pages an identical engine computed for the
        identical prefix, so everything downstream — suffix prefill,
        decode reads — is bit-identical to having prefilled them here."""
        idx = np.asarray(pages, np.int32)
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.cache)
        leaves = []
        for path, leaf in flat:
            pay = sealed.payload[jax.tree_util.keystr(path)]
            assert pay.shape[1] == len(pages) and \
                pay.shape[2:] == leaf.shape[2:], (pay.shape, leaf.shape)
            leaves.append(leaf.at[:, idx].set(pay))
        self.cache = jax.tree_util.tree_unflatten(treedef, leaves)

    def _adopt_from_tier(self):
        """Pre-admission adoption: for each waiting prompt whose local
        registry match is shorter than what the shared tier holds, install
        the missing pages and register the chain — the allocator's
        registry-version bump then makes admission / ``refresh_prefix``
        see an ordinary prefix hit.  Never preempts: under pool pressure
        (alloc returns None) adoption is skipped and the prompt recomputes
        as if the tier did not exist."""
        al = self.alloc
        ps = self.page_size
        for _rid, item in list(self.sched.waiting):
            tokens = item.prompt_tokens() if isinstance(item, SlotState) \
                else item.prompt
            prompt = [int(t) for t in np.asarray(tokens).reshape(-1)]
            want = (len(prompt) - 1) // ps
            if want <= 0 or al.prefix.match(prompt, want).n_pages >= want:
                continue
            sealed = self.prefix_tier.adopt(prompt, want)
            if sealed is None:
                continue
            held = al.match_prefix(prompt, want)     # refs pin the head
            if sealed.n_pages <= len(held):
                al.free_pages(held[::-1])
                continue
            fresh = al.alloc(sealed.n_pages - len(held))
            if fresh is None:                        # pool dry: recompute
                al.free_pages(held[::-1])
                continue
            self._install_pages(sealed.slice(len(held), sealed.n_pages),
                                fresh)
            al.prefix.register(prompt[:sealed.n_pages * ps], held + fresh)
            al.free_pages((held + fresh)[::-1])      # park on the LRU
            self.counters["adopted_pages"] += len(fresh)

    # --- observability ---------------------------------------------------

    def stats(self, check: bool = False) -> Dict:
        """Instantaneous serving gauges + the cumulative ``counters``.

        Invariants the engine maintains (asserted in the tests, logged per
        tick by serve_bench): occupied slots partition into decode-active +
        prefilling; in the paged layout ``pages_in_use + pages_free +
        pages_cached_lru == pages_capacity`` and every prefilling slot's
        pending rows fit the pages it reserved.  ``check=True`` also sweeps
        ``BlockAllocator.check_invariants()`` — O(n_pages), so the tests'
        per-tick assertions opt in while bench/monitoring reads (which time
        the step loop) stay cheap.

        The payload is the frozen, versioned schema in
        ``repro.serve.stats`` (carried under ``schema_version``) and is
        validated against it on every read — the router,
        ``serve_bench.py``, and ``check_regression.py`` all consume the
        same key sets."""
        pre = [self.sched.slots[b] for b in self.sched.prefilling]
        chunk = self.max_prefill_chunk
        pending = [st.prompt_len - st.prefill_pos for st in pre]
        g = dict(
            schema_version=stats_schema.STATS_SCHEMA_VERSION,
            waiting=len(self.sched.waiting),
            decode_slots_active=len(self.sched.decoding),
            prefill_slots=len(pre),
            free_slots=self.sched.n_free,
            prefill_tokens_pending=sum(pending),
            prefill_chunks_pending=sum(
                -(-p // chunk) if chunk else 1 for p in pending),
            spec_k=self.spec_k,
        )
        if self.layout == "paged":
            al = self.alloc
            if check:
                al.check_invariants()
            g.update(pages_in_use=al.live,
                     pages_free=al.free_list_pages,
                     pages_cached_lru=al.lru_pages,
                     pages_capacity=al.capacity,
                     tp=self.tp)
        g["counters"] = dict(self.counters)
        return stats_schema.validate_stats(g, paged=self.layout == "paged")

    def hot_graphs(self) -> Dict[str, tuple]:
        """``name -> (jitted_fn, example_args)`` for every compiled hot
        graph of this engine, with representative arguments built from the
        live state (the real donated cache pytree, zero tokens, the current
        block tables).

        This is the introspection surface ``repro.analysis.jaxpr_audit``
        walks: ``jax.make_jaxpr(fn)(*args)`` / ``fn.lower(*args)`` only
        *trace* the graphs, so the donated cache is never consumed and the
        engine keeps serving afterwards.  Paged engines expose ``decode``,
        ``prefill_chunk`` (one padded chunk at batch 1, the shape
        ``_run_chunk`` compiles) and — when speculation is on — ``verify``;
        the contiguous layout exposes ``decode`` only (its prefill builds a
        private non-donated cache per request)."""
        tok = jnp.zeros((self.batch, 1), jnp.int32)
        pos = jnp.asarray(self.pos)
        if self.layout != "paged":
            return {"decode": (self._decode,
                               (self.folded, self.cache, tok, pos))}
        btab = jnp.asarray(self.block_tables)
        graphs: Dict[str, tuple] = {
            "decode": (self._decode,
                       (self.folded, self.cache, tok, pos, btab)),
        }
        chunk = self.max_prefill_chunk or 2 * self.page_size
        chunk = pages_needed(min(chunk, self.smax),
                             self.page_size) * self.page_size
        graphs["prefill_chunk"] = (self._prefill, (
            self.folded, self.cache, jnp.zeros((1, chunk), jnp.int32),
            btab[:1], jnp.int32(0)))
        if self.spec_k:
            graphs["verify"] = (self._verify, (
                self.folded, self.cache,
                jnp.zeros((self.batch, self.spec_k + 1), jnp.int32),
                pos, btab, jnp.ones((self.batch,), jnp.int32)))
        return graphs

    # --- contiguous-layout helpers ---------------------------------------

    def _bucket_len(self, ln: int) -> int:
        """Padded one-shot prefill length for the contiguous layout: a
        multiple of prefill_bucket so compiled shapes are reused.  (Paged
        chunks pad to whole pages instead — see _run_chunk.)"""
        return min(max(self.prefill_bucket,
                       math.ceil(ln / self.prefill_bucket)
                       * self.prefill_bucket), self.smax)

    def _set_table_row(self, b: int, pages: List[int]):
        self.block_tables[b, :] = 0
        self.block_tables[b, :len(pages)] = pages

    # --- request lifecycle ----------------------------------------------

    def submit(self, request: Request) -> int:
        ln = len(request.prompt)
        # hard validation, not an assert: max_new_tokens >= 1 is what makes
        # the ln + max_new - 1 page reservation always cover the prefill
        # scatter's whole-page padding (pages_needed(ln) rows)
        if ln < 1 or request.max_new_tokens < 1:
            raise ValueError(
                f"request needs a non-empty prompt and max_new_tokens >= 1 "
                f"(got prompt len {ln}, max_new_tokens "
                f"{request.max_new_tokens})")
        if (not self.cfg.sliding_window
                and ln + request.max_new_tokens > self.max_len):
            raise ValueError(
                f"request needs {ln + request.max_new_tokens} cache rows, "
                f"engine max_len={self.max_len}")
        if self.layout == "paged":
            worst = pages_needed(ln + request.max_new_tokens - 1,
                                 self.page_size)
            if worst > self.alloc.capacity:
                raise ValueError(
                    f"request needs up to {worst} cache pages, pool has "
                    f"{self.alloc.capacity} (n_pages={self.n_pages})")
        rid = self.sched.submit(request)
        request.rid = rid
        request.status = RequestStatus.WAITING
        request.finish_reason = None
        request.out = None
        self.requests[rid] = request
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a request anywhere in its lifecycle.  A seated request's
        pages are freed through the ordinary eviction path (the same code
        completion runs), a waiting one is removed from the queue; either
        way the request goes CANCELLED, its partial tokens land in
        ``.out``, and the next ``poll()`` emits the terminal event.
        Returns False when ``rid`` is unknown or already terminal."""
        req = self.requests.get(rid)
        if req is None:
            return False
        for b, st in enumerate(self.sched.slots):
            if st is not None and st.rid == rid:
                st = self.sched.evict(b)       # frees the page chain
                self.pos[b] = 0
                if self.layout == "paged":
                    self.block_tables[b, :] = 0
                emitted = st.emitted
                break
        else:
            item = self.sched.remove_waiting(rid)
            assert item is not None, f"rid {rid} tracked but not found"
            # a preempted SlotState kept its emitted tokens; a plain queued
            # request has none (its pages were already freed at preemption)
            emitted = item.emitted if isinstance(item, SlotState) else []
        self._terminate(rid, req, emitted, RequestStatus.CANCELLED,
                        "cancelled")
        self.counters["cancelled"] += 1
        return True

    def _terminate(self, rid: int, req: Request, emitted: List[int],
                   status: RequestStatus, reason: str):
        """Move a request to a terminal exit and queue its final event.
        ``rid`` is passed explicitly (not read off ``req.rid``): a router
        re-stamps ``req.rid`` with its own global id, while the engine's
        table and event stream stay keyed by the engine-local rid."""
        self.requests.pop(rid, None)
        req.out = np.asarray(emitted, np.int32)
        req.status = status
        req.finish_reason = reason
        self._events.append(TokenEvent(rid, None, len(emitted), True,
                                       reason))

    def _shed_expired(self):
        """Shed WAITING requests whose ``deadline_tick`` has passed (run at
        the head of every poll, before admission): they leave through the
        same terminal path as cancellation — a shed request can never be
        holding pages (a queued request has none; a preempted SlotState's
        were freed at preemption), so the pool cannot be poisoned."""
        if not self.sched.waiting:
            return
        t = self.counters["ticks"]
        for rid, item in [(r, i) for r, i in self.sched.waiting]:
            req = item.request if isinstance(item, SlotState) else item
            if req.deadline_tick is None or t < req.deadline_tick:
                continue
            self.sched.remove_waiting(rid)
            emitted = item.emitted if isinstance(item, SlotState) else []
            self._terminate(rid, req, emitted, RequestStatus.CANCELLED,
                            "deadline")
            self.counters["shed_deadline"] += 1

    def _pick_token(self, logits_row: np.ndarray, req: Request) -> int:
        if req.temperature > 0:
            z = logits_row / max(req.temperature, 1e-4)
            z = z + self.rng.gumbel(size=z.shape)
            return int(np.argmax(z))
        return int(np.argmax(logits_row))

    def _prefill_request(self, req: Request) -> Tuple[np.ndarray, object, int]:
        """Contiguous layout: build the batch-1 cache for a prompt; returns
        (last-position logits (V,), cache1, prompt_len)."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        ln = len(prompt)
        if self._attn_only and ln <= self.smax:
            # one-shot: pad to a bucket so compiled prefill shapes are reused;
            # a pad row at cache index r is overwritten by the decode step at
            # pos == r — the same step whose mask first admits index r — so
            # pad garbage is never attended
            bl = self._bucket_len(ln)
            toks = np.zeros((1, bl), np.int32)
            toks[0, :ln] = prompt
            logits, cache1 = self._prefill(self.folded, jnp.asarray(toks))
            return np.asarray(logits[0, ln - 1]), cache1, ln
        # recurrence (SSM/hybrid) or over-long SWA prompt: batch-1 decode loop
        cache1 = S.init_cache(self.cfg, 1, self.max_len)
        logits = None
        for t in range(ln):
            logits, cache1 = self._decode(
                self.folded, cache1, jnp.asarray(prompt[t].reshape(1, 1)),
                jnp.asarray(np.asarray([t], np.int32)))
            self.counters["loop_prefill_steps"] += 1
        return np.asarray(logits[0, -1]), cache1, ln

    def _run_chunk(self, b: int, st: SlotState, pos0: int, ntok: int
                   ) -> List[TokenEvent]:
        """One prefill chunk for slot ``b``: rows [pos0, pos0+ntok) of the
        prompt through the chunk forward.  On the FINAL chunk the last real
        row's logits hand the request straight into decode (first token
        sampled, no extra forward); mid-prompt chunks emit nothing.

        Paged: the chunk scatters its K/V through a local block-table row
        and attends over the slot's whole mapped chain (prior chunks +
        shared prefix pages read directly from the page pool).  The engine's
        shared ``block_tables`` row stays zeroed (trash page) until handoff,
        so decode ticks running while this slot is mid-prefill cannot
        scribble on its pages.  Contiguous: a single whole-prompt chunk via
        the batch-1 prefill + slot write (chunking needs pages).

        A restored preempted slot runs through this same path — its
        ``prompt_tokens`` replay sequence includes any tokens it emitted
        before the spill.  Each chunk charges ``recomputed_tokens`` for the
        rows it re-runs below the slot's high-water mark (the furthest row
        ever computed, across every spill) — rows the prefix registry gave
        back are skipped by the cursor and never charged."""
        req = st.request
        prompt = np.asarray(st.prompt_tokens(), np.int32).reshape(-1)
        ln = len(prompt)
        if pos0 < st.hwm_rows:
            self.counters["recomputed_tokens"] += \
                min(pos0 + ntok, st.hwm_rows) - pos0
        final = pos0 + ntok >= ln
        loop_prefill = False
        if self.layout == "paged":
            # ragged last chunk pads to whole pages (the scatter writes
            # whole pages); pad rows sit causally after every real query
            # and are overwritten by the decode step at their position
            pad = pages_needed(ntok, self.page_size) * self.page_size
            toks = np.zeros((1, pad), np.int32)
            toks[0, :ntok] = prompt[pos0:pos0 + ntok]
            btab = np.zeros((1, self.max_blocks), np.int32)
            btab[0, :len(st.pages)] = st.pages
            logits, self.cache = self._prefill(
                self.folded, self.cache, jnp.asarray(toks),
                jnp.asarray(btab), jnp.int32(pos0))
            last = None
            if final:
                with _span("serve.fetch", what="chunk"):
                    last = np.asarray(logits[0, ntok - 1])
        else:
            assert pos0 == 0 and final, \
                "contiguous layout prefills in one whole-prompt chunk"
            loop_prefill = not (self._attn_only and ln <= self.smax)
            last, cache1, _ = self._prefill_request(req)
            self.cache = self._write_slot(self.cache, cache1, jnp.int32(b))
        st.prefill_pos = pos0 + ntok
        st.chunks_done += 1
        self.counters["prefill_tokens"] += ntok
        self.counters["prefill_chunks"] += 1
        if not final:
            return []
        # --- handoff into decode (no extra forward) ---
        if self.layout == "paged":
            ptoks = [int(t) for t in prompt]
            self.alloc.register_prefix(ptoks, st.pages)
            if self.prefix_tier is not None:
                self._publish_prefix(ptoks)
            self._set_table_row(b, st.pages)
        # the replay snapshot is spent: decode appends to ``emitted`` from
        # here, so keeping it would silently desync prompt_tokens(); the
        # next spill (if any) rebuilds it from prompt + emitted
        st.tokens = None
        if st.shared_rows:
            self.counters["prefix_hits"] += 1
            self.counters["shared_rows"] += st.shared_rows
            if st.chunks_done == 1:
                self.counters["suffix_prefills"] += 1
        elif st.chunks_done == 1 and not loop_prefill:
            self.counters["oneshot_prefills"] += 1
        if st.chunks_done > 1:
            self.counters["chunked_prefills"] += 1
        self.pos[b] = ln
        st.pos = ln
        tok = self._pick_token(last, req)
        st.last_token = tok
        st.emitted.append(tok)
        req.status = RequestStatus.DECODE
        if self._done(st):
            self._finish(b)
        return [TokenEvent(st.rid, tok, len(st.emitted) - 1,
                           req.status.terminal, req.finish_reason)]

    def _finish(self, b: int):
        st = self.sched.evict(b)        # paged: returns the page chain
        req = self.requests.pop(st.rid)
        req.out = np.asarray(st.emitted, np.int32)
        req.status = RequestStatus.FINISHED
        req.finish_reason = "eos" if (
            req.eos_token is not None and st.emitted
            and st.emitted[-1] == req.eos_token) else "length"
        self.pos[b] = 0
        if self.layout == "paged":
            self.block_tables[b, :] = 0
        self.counters["completed"] += 1

    # --- on-demand growth + preemption -----------------------------------

    def _preempt(self, b: int):
        """Spill slot ``b`` (scheduler registers its finished pages and
        requeues it at the queue front) and clear its engine-side rows."""
        st = self.sched.slots[b]
        was_prefilling = st.prefilling
        self.sched.preempt(b)
        st.request.status = RequestStatus.WAITING
        self.pos[b] = 0
        self.block_tables[b, :] = 0
        self.counters["preemptions"] += 1
        self.counters["preempted_prefill" if was_prefilling
                      else "preempted_decode"] += 1
        self.counters["spilled_rows"] += st.spilled_rows

    def _grow_rows(self, b: int, st: SlotState, rows: int):
        """Grow slot ``b``'s page chain to cover ``rows`` cache rows,
        preempting victims while the pool is dry.  ``submit`` caps every
        request's worst-case pages at pool capacity (speculative rows
        included: the per-slot draft budget keeps the furthest verify row
        at plain decode's worst case), so once every other slot is spilled
        the allocation cannot fail — the RuntimeError is a genuine
        invariant breach, not an operating condition."""
        while True:
            got = self.sched.grow(st, rows)
            if got is not None:
                self.counters["grown_pages"] += got
                break
            v = self.sched.pick_victim(exclude=frozenset({b}))
            if v is None:
                raise RuntimeError(
                    "page pool exhausted with no preemption victim; "
                    "submit() sizing makes this unreachable")
            self._preempt(v)
        if got:                         # chain unchanged -> row already set
            self._set_table_row(b, st.pages)

    def _grow_decode_pages(self):
        """On-demand mode, run between the tick's prefill chunks and its
        decode forward: make sure every decoding slot owns the page its
        write cursor is about to enter.  Slots grow oldest-first; when the
        pool comes up empty the scheduler names a victim (last-admitted
        prefilling slot, else longest-remaining decoder — never the oldest
        seated request while another candidate exists) which is spilled and
        the allocation retried."""
        order = sorted(self.sched.decoding,
                       key=lambda b: self.sched.slots[b].rid)
        for b in order:
            st = self.sched.slots[b]
            if st is None:              # preempted by an earlier grower
                continue
            self._grow_rows(b, st, st.pos + 1)

    def _done(self, st: SlotState) -> bool:
        req = st.request
        if len(st.emitted) >= req.max_new_tokens:
            return True
        return req.eos_token is not None and st.emitted and \
            st.emitted[-1] == req.eos_token

    # --- speculative decode (draft-then-verify) --------------------------

    def _spec_tick(self) -> Optional[List[TokenEvent]]:
        """One speculative decode tick: draft, verify, greedy-accept.

        Replaces the plain (B, 1) decode forward with a single (B,
        ``spec_k``+1) verify forward when at least one slot has draft
        proposals.  Per slot the verify rows are ``[last_token, d_1, ...,
        d_n]`` at cache positions ``pos .. pos+n``; row ``j``'s logits are
        what plain decode would have produced after committing the first
        ``j`` proposals, so greedily accepting while ``d_j == argmax(row
        j-1)`` is bit-identical to running plain decode ``n_acc+1`` times
        (the final row's argmax is the free "bonus" token).  The write
        cursor (``st.pos`` / ``self.pos``) advances only over committed
        tokens — rejected tail rows hold garbage K/V *past* the cursor,
        which the next forward overwrites write-before-read, so rollback
        is a no-op on the allocator.

        Phases:

        1. propose — ask the draft source for up to ``k_b`` tokens per
           greedy decoding slot, where ``k_b`` caps at the slot's
           remaining ``max_new_tokens`` budget minus the bonus token
           (keeps the furthest verify row at plain decode's worst case,
           so ``submit``'s page-cap invariant is untouched).  Sampling
           slots (temperature > 0) are never drafted for: acceptance is
           exact argmax matching.  No proposals anywhere -> return None
           and let the plain decode graph run.
        2. grow (on-demand reservation only) — extend each proposing
           slot's page chain to cover its verify rows, in rid order,
           preempting victims like :meth:`_grow_decode_pages`.  A slot
           preempted by an earlier grower drops its proposals.
        3. verify — ONE forward at the fixed compiled shape (B,
           ``spec_k``+1); non-proposing slots ride along with one real
           row (their plain decode step), padding rows scatter to the
           trash page.
        4. accept — per slot, walk rows while proposals match the argmax
           chain; emit accepted tokens + the first divergent/bonus token,
           truncated by ``max_new_tokens``/EOS exactly as plain decode
           would be.  Counters: ``drafted``/``accepted``/``rejected`` and
           ``accept_len_hist`` (accepted-prefix length -> slot-tick
           count); the forward charges one ``decode_steps``.
        """
        active = self.sched.decoding
        props: Dict[int, List[int]] = {}
        for b in active:
            st = self.sched.slots[b]
            req = st.request
            if req.temperature > 0:
                continue                    # greedy acceptance only
            k_b = min(self.spec_k,
                      req.max_new_tokens - len(st.emitted) - 1)
            if k_b <= 0:
                continue
            ctx = np.concatenate(
                [np.asarray(req.prompt, np.int64).reshape(-1),
                 np.asarray(st.emitted, np.int64)])
            p = [int(t) for t in self.draft.propose(ctx, k_b)[:k_b]]
            if p:
                props[b] = p
        if not props:
            return None                     # plain decode graph this tick
        if self.reserve_policy == "ondemand":
            for b in sorted(props, key=lambda i: self.sched.slots[i].rid
                            if self.sched.slots[i] is not None else -1):
                st = self.sched.slots[b]
                if st is None:              # preempted by an earlier grower
                    continue
                self._grow_rows(b, st, st.pos + 1 + len(props[b]))
        active = self.sched.decoding        # growth may have preempted
        live = set(active)
        props = {b: p for b, p in props.items() if b in live}
        if not props:
            return None
        with _span("serve.decode", rows=len(active)):
            toks = np.zeros((self.batch, self.spec_k + 1), np.int32)
            nrows = np.ones((self.batch,), np.int32)
            for b in active:
                toks[b, 0] = self.sched.slots[b].last_token
            for b, p in props.items():
                toks[b, 1:1 + len(p)] = p
                nrows[b] = 1 + len(p)
            logits, self.cache = self._verify(
                self.folded, self.cache, jnp.asarray(toks),
                jnp.asarray(self.pos), jnp.asarray(self.block_tables),
                jnp.asarray(nrows))
        with _span("serve.fetch", what="verify"):
            rows = np.asarray(logits)       # (B, spec_k+1, V)
        with _span("serve.pick"):
            return self._accept(active, props, rows)

    def _accept(self, active, props: Dict[int, List[int]], rows: np.ndarray
                ) -> List[TokenEvent]:
        """Phase 4 of :meth:`_spec_tick`: greedy acceptance over the
        verify rows, emission and the speculative counters."""
        events: List[TokenEvent] = []
        n_emitted = 0
        for b in active:
            st = self.sched.slots[b]
            req = st.request
            p = props.get(b, [])
            n_prop = len(p)
            emit: List[int] = []
            j = 0
            while True:
                tok = self._pick_token(rows[b, j], req)
                emit.append(tok)
                if len(st.emitted) + len(emit) >= req.max_new_tokens or (
                        req.eos_token is not None and tok == req.eos_token):
                    break                   # request finishes on this token
                if j < n_prop and p[j] == tok:
                    j += 1                  # proposal matched: next row
                    continue
                break                       # divergence: tok is the repair
            if n_prop:
                # accepted prefix length (the loop only advances on
                # matches, so matching positions form a prefix of emit)
                n_acc = sum(1 for i in range(min(len(emit), n_prop))
                            if p[i] == emit[i])
                self.counters["drafted"] += n_prop
                self.counters["accepted"] += n_acc
                self.counters["rejected"] += n_prop - n_acc
                h = self.counters["accept_len_hist"]
                h[n_acc] = h.get(n_acc, 0) + 1
            for tok in emit:
                st.last_token = tok
                st.emitted.append(tok)
                self.pos[b] += 1
                st.pos += 1
                done = self._done(st)
                if done:
                    self._finish(b)
                events.append(TokenEvent(st.rid, tok, len(st.emitted) - 1,
                                         req.status.terminal,
                                         req.finish_reason))
                if done:
                    break
            n_emitted += len(emit)
        self.counters["decode_steps"] += 1
        self.counters["decode_tokens"] += n_emitted
        return events

    # --- the engine loop ------------------------------------------------

    def poll(self) -> List[TokenEvent]:
        """One scheduler tick of the token-budget loop:

        0. shed WAITING requests whose ``deadline_tick`` has passed, and
           flush terminal events queued by ``cancel()`` since last tick,
        1. seat waiting requests into free slots (paged: reserve their page
           budget; prefill does NOT run here),
        2. run prefill chunks for prefilling slots under the tick's token
           budget (``max_batched_tokens`` minus this tick's decode tokens;
           a final chunk also charges the decode token of its handoff),
           replanning after every chunk so a completion's registered prefix
           is visible to the next slot's first chunk,
        3. (on-demand reservation) grow each decoding slot's page chain
           where its write cursor crosses a page boundary, preempting a
           victim when the pool runs dry,
        4. decode one token for every slot whose prompt is fully cached
           (slots that handed off in step 2 join the same tick's batch).
           With ``spec_k > 0`` and at least one slot holding draft
           proposals, step 4 instead runs one multi-row verify forward
           (:meth:`_spec_tick`) that can commit several tokens per slot.

        Returns this tick's :class:`TokenEvent` stream, in emission order.
        Every request's stream ends with exactly one ``final`` event; a
        cancelled/shed request's final event carries ``token=None``.

        The tick and its phases are ``jax.profiler.TraceAnnotation`` spans
        named in ``stats.SPANS``: free while no profiler runs, and on the
        device trace's clock while one does."""
        self.counters["ticks"] += 1
        with _span("serve.tick", tick=self.counters["ticks"]):
            return self._tick()

    def _tick(self) -> List[TokenEvent]:
        with _span("serve.admit"):
            self._shed_expired()
            events = self._events        # cancel/shed events queued so far
            self._events = []
            if self.prefix_tier is not None and self.sched.waiting:
                self._adopt_from_tier()  # before admission: adopted pages
                #                          surface as ordinary prefix hits
            placed = self.sched.admit()
            for _b, st in placed:
                st.request.status = RequestStatus.PREFILL
                if st.preemptions:      # a spilled request re-seated
                    self.counters["restores"] += 1
            if self.layout == "paged" and self.sched.waiting \
                    and self.sched.n_free > 0:
                # a request is waiting on PAGES, not slots: the stranded-
                # capacity signal the overload bench A/Bs across policies
                self.counters["pool_wait_ticks"] += 1
        n_decode = len(self.sched.decoding)
        used = 0
        chunked: set = set()
        while True:
            plan = self.sched.next_chunk(n_decode, used,
                                         exclude=frozenset(chunked))
            if plan is None:
                break
            b, st, pos0, ntok = plan
            chunked.add(b)
            # a final chunk hands the slot into this tick's decode batch:
            # charge its decode token so the budget stays a real cap
            used += ntok + (pos0 + ntok >= st.prompt_len)
            with _span("serve.chunk", rid=st.rid, slot=b, pos0=pos0,
                       ntok=ntok):
                events.extend(self._run_chunk(b, st, pos0, ntok))
        for b in self.sched.prefilling:   # scheduler anti-starvation input
            st = self.sched.slots[b]
            st.starved_ticks = 0 if b in chunked else st.starved_ticks + 1
        if self.layout == "paged" and self.reserve_policy == "ondemand":
            with _span("serve.grow"):
                self._grow_decode_pages()  # may preempt victims
        active = self.sched.decoding
        if self.layout == "paged":
            self.counters["cache_pages_peak"] = self.alloc.peak_live
        if not active:
            return events
        if self.spec_k and self.draft is not None:
            spec = self._spec_tick()
            if spec is not None:            # verify forward ran this tick
                events.extend(spec)
                self.counters["cache_pages_peak"] = self.alloc.peak_live
                return events
            # no proposals anywhere: fall through to plain decode
        with _span("serve.decode", rows=len(active)):
            toks = np.zeros((self.batch, 1), np.int32)
            for b in active:
                toks[b, 0] = self.sched.slots[b].last_token
            extra = ((jnp.asarray(self.block_tables),)
                     if self.layout == "paged" else ())
            logits, self.cache = self._decode(
                self.folded, self.cache, jnp.asarray(toks),
                jnp.asarray(self.pos), *extra)
        with _span("serve.fetch", what="decode"):
            rows = np.asarray(logits[:, -1])      # (B, V)
        with _span("serve.pick"):
            for b in active:
                st = self.sched.slots[b]
                req = st.request
                self.pos[b] += 1
                st.pos += 1
                tok = self._pick_token(rows[b], req)
                st.last_token = tok
                st.emitted.append(tok)
                if self._done(st):
                    self._finish(b)
                events.append(TokenEvent(st.rid, tok, len(st.emitted) - 1,
                                         req.status.terminal,
                                         req.finish_reason))
        self.counters["decode_steps"] += 1
        self.counters["decode_tokens"] += len(active)
        return events

    @property
    def has_work(self) -> bool:
        """True while a poll() could still produce events: live requests
        anywhere in the pipeline, or queued terminal events."""
        return bool(self._events) or self.sched.has_work

    def step(self) -> List[Tuple[int, int]]:
        """Back-compat wrapper over :meth:`poll`: one tick, returning the
        (rid, token) pairs emitted (token-less terminal events dropped)."""
        return [(e.rid, e.token) for e in self.poll()
                if e.token is not None]

    def run(self) -> List[Tuple[int, int]]:
        """Drain the queue; returns every (rid, token) emitted."""
        out = []
        while self.has_work:
            out.extend(self.step())
        return out

    def generate(self, requests: List[Request]) -> List[Request]:
        """Batch convenience API: submit everything, drain, return the same
        requests with ``.out`` filled (continuous batching inside)."""
        for r in requests:
            self.submit(r)
        self.run()
        return requests


class LockstepEngine:
    """The original lockstep engine: one shared position scalar, prefill
    replayed through the decode graph for the whole (same-length) batch.
    Kept as the serve_bench baseline and for archs the continuous engine
    doesn't take (audio codebooks)."""

    def __init__(self, cfg: ModelConfig, folded,
                 config: Optional[EngineConfig] = None, **kw):
        config = _config_only(config, kw, "LockstepEngine")
        self.cfg = cfg
        self.folded = folded
        self.config = config
        self.batch = config.batch_slots
        self.max_len = config.max_len
        self.cache = S.init_cache(cfg, self.batch, self.max_len)
        self.pos = np.zeros(self.batch, np.int32)
        self.key = jax.random.PRNGKey(config.seed)

        def decode_step(folded_, cache, tok, pos):
            return S.serve_forward(cfg, folded_, tok, cache=cache,
                                   pos_offset=pos, mode="decode")

        self._decode = jax.jit(decode_step, donate_argnums=(1,))

    def reset(self, seed: int = 0):
        self.cache = S.init_cache(self.cfg, self.batch, self.max_len)
        self.pos = np.zeros(self.batch, np.int32)
        self.key = jax.random.PRNGKey(seed)

    def _step(self, tokens_col: np.ndarray, pos_scalar: int):
        tok = jnp.asarray(tokens_col).reshape(self.batch, 1)
        logits, self.cache = self._decode(self.folded, self.cache, tok,
                                          jnp.int32(pos_scalar))
        return logits[:, -1] if logits.ndim == 3 else logits[:, :, -1]

    def generate(self, requests: List[Request]) -> List[Request]:
        """Lockstep decode for a batch of same-length-padded prompts."""
        assert len(requests) <= self.batch
        max_prompt = max(len(r.prompt) for r in requests)
        max_new = max(r.max_new_tokens for r in requests)
        toks = np.zeros((self.batch, max_prompt), np.int32)
        for i, r in enumerate(requests):
            toks[i, max_prompt - len(r.prompt):] = r.prompt  # left-pad
        outs = [[] for _ in requests]
        # prefill via lockstep decode (works uniformly for attn/ssm/hybrid)
        last_logits = None
        for t in range(max_prompt):
            last_logits = self._step(toks[:, t], t)
        cur = np.asarray(jnp.argmax(last_logits, -1)).astype(np.int32)
        for i in range(len(requests)):
            outs[i].append(int(cur[i]))
        for t in range(max_prompt, max_prompt + max_new - 1):
            logits = self._step(cur, t)
            if any(r.temperature > 0 for r in requests):
                self.key, sub = jax.random.split(self.key)
                samp = jax.random.categorical(sub, logits / max(
                    requests[0].temperature, 1e-4), -1)
                cur = np.asarray(samp).astype(np.int32)
            else:
                cur = np.asarray(jnp.argmax(logits, -1)).astype(np.int32)
            for i in range(len(requests)):
                if len(outs[i]) < requests[i].max_new_tokens:
                    outs[i].append(int(cur[i]))
        for r, o in zip(requests, outs, strict=True):
            r.out = np.asarray(o, np.int32)
            r.status = RequestStatus.FINISHED
            r.finish_reason = "length"
        return requests
