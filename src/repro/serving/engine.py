"""Batched decode serving engine with tiered KV-cache placement.

The paper's technique as a runtime feature: the KV cache can live in a
"smaller/faster effective tier" via int8 quantization (kv_policy="int8" —
halves decode attention traffic, the TPU analogue of restricting Q/K/V
traffic to the fast tier, takeaway III), or plain bf16/f32
(kv_policy="native"). Throughput is reported in TPS — the paper's
interactivity metric — and the analytical model (repro.core) predicts the
same engine's behaviour on NPU+HBS/chiplet hierarchies.

Two batching models (DESIGN.md SS9/SS10):

* ``scheduler="static"`` — batch waves over equal-length prompts
  (bucketed); per-wave prefill then lock-step decode with early exit when
  every sequence has emitted EOS.
* ``scheduler="continuous"`` — iteration-level batching over a paged,
  tiered KV cache: requests join/retire per decode step, pages come from a
  pool capped by a ``TierBudget`` derived from a ``MemoryHierarchy``, and
  pool exhaustion preempts the youngest request (recompute-style). When
  the budget has an offload tier (HBS), per-page residency is real: cold
  pages spill, a block-aligned prefetch runs ahead of the fused decode
  loop, and migration time the kernels outrun is charged as recorded
  stall on a virtual clock (DESIGN.md SS13) — TPS/TTFT/ITL then price the
  HBS bandwidth/latency envelope while outputs stay token-identical. With
  the native kv_policy, greedy outputs are token-identical to the static
  engine; under int8 the schedulers can diverge within quantization error,
  because the shared page pool calibrates scales once (first prefill)
  while the static engine recalibrates per wave (DESIGN.md SS3).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import (RuntimeOptions, copy_pages, decode_step,
                          decode_steps, decode_steps_paged, init_cache,
                          init_paged_cache, init_params, layer_dma_slices,
                          paged_supported, prefill, prefill_paged_chunk,
                          spec_decode_verify)
from repro.models import sampling
from repro.serving import metrics
from repro.serving.kv_manager import (PagedKVManager, SimulatedTierDevice,
                                      TierBudget, page_bytes)
from repro.serving.scheduler import (PREFILLING, RUNNING, AdaptiveSpecK,
                                     ContinuousScheduler, Request)
from repro.serving.streams import VirtualStream
from repro.serving.trace import DECODE, DRAFT, STALL, TraceRecorder
from repro.sharding.rules import auto_mesh


def _next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1)."""
    p = 1
    while p < n:
        p *= 2
    return p


def _program(fn, *args, **kwargs):
    """``partial(fn, ...)`` under ``fn``'s name: jitted, it lowers to
    module ``jit_<fn>`` and runs under that name in a device trace (a
    bare partial runs as ``jit__unknown``). Only the name is copied, so
    JAX still reads the partial's own signature."""
    p = partial(fn, *args, **kwargs)
    p.__name__ = p.__qualname__ = fn.__name__
    return p


def _pad_pow2(items: List, pad_item) -> List:
    """Pad a work list to the next power-of-two length with inert filler so
    jitted consumers see O(log n) distinct shapes instead of one compile
    per batch size (used for COW copy batches; fused decode blocks clamp
    their step count through the same ``_next_pow2`` rounding)."""
    return list(items) + [pad_item] * (_next_pow2(len(items)) - len(items))


@dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    # serve makespan on the virtual stream clock (SS16): max over the
    # prefill/decode streams' horizons, summed across serve() calls. With
    # overlap it is LESS than prefill_s + decode_s — that gap is the
    # overlapped time, and what tps prices.
    serve_s: float = 0.0
    new_tokens: int = 0
    requests: int = 0
    decode_steps: int = 0
    preemptions: int = 0
    # chunked prefill + prefix sharing observability (continuous scheduler)
    prefill_tokens_computed: int = 0    # chunk tokens actually run
    cached_prefix_tokens: int = 0       # prompt tokens served from the cache
    pages_deduped: int = 0              # page allocations avoided by sharing
    cow_copies: int = 0
    peak_pages_used: int = 0            # max distinct in-use pages
    prefill_compiles: int = 0           # distinct jitted prefill shapes
    # fused multi-step decode observability (DESIGN.md SS12)
    host_syncs: int = 0                 # device->host round-trips taken
    decode_compiles: int = 0            # distinct jitted decode shapes
    # HBS page offload (DESIGN.md SS13): migration traffic + decode stalls
    # charged in virtual seconds by the SimulatedTierDevice
    stall_s: float = 0.0                # kernel launches waiting on fetches
    spill_bytes: float = 0.0            # dirty write-back traffic (out)
    fetch_bytes: float = 0.0            # offload -> fast migration traffic
    pages_spilled: int = 0
    pages_fetched: int = 0
    peak_fast_pages: int = 0            # max fast-tier (non-offload) pages
    prefetch_hits: int = 0              # fetches that beat their kernel
    prefetch_misses: int = 0            # fetches a kernel had to wait on
    # SS17: per-direction DMA bytes keyed "src->dst" at each link boundary
    # (write-back vs fetch vs chiplet promote/demote made visible)
    channel_bytes: Dict[str, float] = field(default_factory=dict)
    clean_demotions: int = 0            # spills that skipped write-back
    # chiplet promotion level (SS17)
    chiplet_promotions: int = 0
    chiplet_demotions: int = 0
    tier_touches: Dict[str, int] = field(default_factory=dict)
    # stall the layer-sliced overlap hid vs the whole-block barrier
    # counterfactual (0 when --no-layer-overlap)
    stall_saved_s: float = 0.0
    # runtime -> analytic bridge: the landed-page tier split observed at
    # peak occupancy, pin-able into core.concurrency.concurrent_inference
    kv_split_at_peak: tuple = ()
    # speculative decoding (DESIGN.md SS14)
    draft_proposed: int = 0             # draft tokens fed to verify passes
    draft_accepted: int = 0             # draft tokens the target kept
    spec_blocks: int = 0                # verify passes run
    # per-request attribution (SS13 deferred item): residency stall charged
    # to the requests whose pages actually gated each barrier
    stall_by_rid: Dict[int, float] = field(default_factory=dict)
    # per-request latency samples (seconds)
    ttft: List[float] = field(default_factory=list)
    itl: List[float] = field(default_factory=list)

    @property
    def prefetch_hit_rate(self) -> float:
        n = self.prefetch_hits + self.prefetch_misses
        return self.prefetch_hits / n if n else 1.0

    @property
    def chiplet_hit_rate(self) -> float:
        """Fraction of landed-page kernel reads served from the chiplet
        level (0.0 when no chiplet tier is configured)."""
        total = sum(self.tier_touches.values())
        return (self.tier_touches.get("chiplet", 0) / total
                if total else 0.0)

    @property
    def acceptance_rate(self) -> float:
        return (self.draft_accepted / self.draft_proposed
                if self.draft_proposed else 0.0)

    @property
    def tps(self) -> float:
        """Decode tokens/sec over the full request (paper's metric):
        tokens over the stream-clock makespan when one was recorded
        (continuous engine), else over summed phase time (static
        engine, where the two coincide)."""
        t = (self.serve_s if self.serve_s > 0
             else self.prefill_s + self.decode_s)
        return self.new_tokens / t if t > 0 else 0.0

    def _pct(self, xs: List[float], q: float) -> float:
        return metrics.percentile(xs, q)

    @property
    def ttft_p50(self) -> float:
        return self._pct(self.ttft, 50)

    @property
    def ttft_p95(self) -> float:
        return self._pct(self.ttft, 95)

    @property
    def itl_p50(self) -> float:
        return self._pct(self.itl, 50)

    @property
    def itl_p95(self) -> float:
        return self._pct(self.itl, 95)


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params=None,
                 opts: RuntimeOptions = RuntimeOptions(dtype="float32"),
                 *, kv_policy: str = "native", max_len: int = 512,
                 eos_id: Optional[int] = None, seed: int = 0,
                 scheduler: str = "static", page_size: int = 16,
                 max_batch: int = 8, n_pages: Optional[int] = None,
                 hierarchy=None, prefill_chunk: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 prefix_cache: bool = True, decode_lookahead: int = 8,
                 offload: bool = True, hbs_gbps: Optional[float] = None,
                 hbs_latency_us: Optional[float] = None,
                 chiplet_gbps: Optional[float] = None,
                 chiplet_latency_us: Optional[float] = None,
                 layer_overlap: bool = True,
                 writeback_link: str = "dedicated",
                 spec_mode: str = "off", spec_k: int = 4, draft_cfg=None,
                 draft_params=None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, sample_seed: int = 0,
                 shards: int = 1, overlap: bool = True):
        import dataclasses
        if kv_policy == "int8":
            opts = dataclasses.replace(opts, cache_dtype="int8")
        # ---- head-sharded multi-device serving (DESIGN.md SS16) ---- #
        # an N-way mesh partitions the paged pool's KV-head dim; each
        # device runs the unchanged kernels on its Hkv/N head slice and
        # the per-head outputs are all-gathered, so outputs stay bitwise
        # identical to shards=1 while per-device page bytes shrink by N
        if shards < 1:
            raise ValueError(f"shards ({shards}) must be >= 1")
        self.mesh = None
        if shards > 1:
            if scheduler != "continuous":
                raise ValueError("head-sharded serving (shards > 1) runs "
                                 "on the paged continuous engine; use "
                                 "scheduler='continuous'")
            if cfg.n_kv_heads % shards:
                raise ValueError(f"shards ({shards}) must divide "
                                 f"n_kv_heads ({cfg.n_kv_heads}) for head "
                                 f"sharding")
            ndev = len(jax.devices())
            if ndev < shards:
                raise ValueError(
                    f"shards={shards} needs {shards} devices but jax sees "
                    f"{ndev}; on CPU export XLA_FLAGS=--xla_force_host_"
                    f"platform_device_count={shards} before importing jax")
            self.mesh = auto_mesh((shards,), ("model",),
                                  devices=jax.devices()[:shards])
            opts = dataclasses.replace(opts, kv_shard_mesh=self.mesh)
        self.shards = shards
        self.overlap = overlap
        if scheduler not in ("static", "continuous"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        if scheduler == "continuous":
            reason = paged_supported(cfg)
            if reason:
                raise NotImplementedError(
                    f"continuous scheduler needs the paged KV path: {reason}")
        # ---- speculative decoding / sampling configuration (SS14) ---- #
        if spec_mode not in ("off", "ngram", "model"):
            raise ValueError(f"spec_mode must be one of off|ngram|model, "
                             f"got {spec_mode!r}")
        if spec_mode != "off" and scheduler != "continuous":
            raise ValueError("speculative decoding runs on the paged "
                             "continuous engine; use scheduler='continuous' "
                             "or spec_mode='off'")
        if spec_mode != "off" and spec_k < 1:
            raise ValueError(f"spec_k ({spec_k}) must be >= 1")
        if spec_mode == "model" and draft_cfg is None:
            raise ValueError("spec_mode='model' needs a draft_cfg "
                             "(a small paged-KV-capable ArchConfig)")
        if draft_cfg is not None and spec_mode != "model":
            raise ValueError(f"draft_cfg is only meaningful with "
                             f"spec_mode='model' (got {spec_mode!r})")
        if temperature < 0.0:
            raise ValueError(f"temperature ({temperature}) must be >= 0")
        if top_k < 0:
            raise ValueError(f"top_k ({top_k}) must be >= 0")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p ({top_p}) must be in (0, 1]")
        if temperature == 0.0 and (top_k or top_p < 1.0):
            raise ValueError("top_k/top_p filter a stochastic sample; they "
                             "need temperature > 0 (temperature 0 is greedy)")
        self.spec_mode = spec_mode
        self.spec_k = spec_k
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.sample_seed = sample_seed
        self.cfg = cfg
        self.opts = opts
        self.max_len = max_len
        self.eos_id = eos_id
        self.scheduler = scheduler
        self.page_size = page_size
        self.max_batch = max_batch
        if decode_lookahead < 1:
            raise ValueError(f"decode_lookahead ({decode_lookahead}) must "
                             f"be >= 1")
        self.decode_lookahead = decode_lookahead
        self.params = params if params is not None else init_params(
            cfg, jax.random.PRNGKey(seed), opts)
        if self.mesh is not None:
            # replicate the weights over the mesh once; left on one device
            # they would be re-copied to every shard on each jitted call
            from jax.sharding import NamedSharding, PartitionSpec
            self.params = jax.device_put(
                self.params, NamedSharding(self.mesh, PartitionSpec()))
        self._prefill = jax.jit(_program(prefill, cfg, opts=opts))
        self._decode = jax.jit(_program(decode_step, cfg, opts=opts),
                               donate_argnums=(3,))
        # fused K-step greedy decode over the dense cache (static engine).
        # temperature/top_k/top_p are compile-time sampling config — the
        # body branches on them on the host, so they must be static (a
        # traced temperature would hit a concretization error)
        self._decode_block = jax.jit(_program(decode_steps, cfg, opts=opts),
                                     static_argnames=("n_steps",
                                                      "temperature",
                                                      "top_k", "top_p"),
                                     donate_argnums=(3,))
        # paged path (continuous scheduler); chunk right-padding needs no
        # reserve headroom — positions past a prompt's pages spill into the
        # reserved null page
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else max(2 * page_size, 32))
        if self.prefill_chunk % page_size:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must be a multiple "
                f"of page_size ({page_size})")
        self.prefill_budget = prefill_budget
        self.prefix_cache = prefix_cache
        self.n_pages_per_seq = -(-max_len // page_size)
        # active KV element width (int8 -> 1 via dtype); threaded through
        # the manager so occupancy/migration pricing never assumes bf16
        self.kv_dtype_bytes = (jnp.dtype(opts.cache_dtype).itemsize
                               if opts.cache_dtype else opts.jdtype.itemsize)
        self.page_nbytes = page_bytes(cfg, page_size, self.kv_dtype_bytes)
        # per-device page slice (SS16): each shard holds Hkv/N heads of
        # every page, so capacity AND migration traffic are charged at
        # page_bytes/N per device — the constrained resource
        self.page_nbytes_shard = self.page_nbytes / shards
        self.tier_budget = (None if hierarchy is None else
                            TierBudget.from_hierarchy(
                                hierarchy, cfg, page_size,
                                self.kv_dtype_bytes, shards=shards))
        # HBS offload timing: migrations between the fast KV tiers and the
        # budget's slowest tier are charged in virtual time (DESIGN.md
        # SS13). ``hbs_gbps``/``hbs_latency_us`` override the hierarchy's
        # offload-level numbers (the CLI/bench sweep lever). A fresh device
        # is built per serve() so channel horizons reset between runs.
        if writeback_link not in ("dedicated", "shared"):
            raise ValueError(f"writeback_link must be 'dedicated' or "
                             f"'shared', got {writeback_link!r}")
        self.writeback_link = writeback_link
        self._tier_device_args = None
        if (offload and hierarchy is not None and self.tier_budget is not None
                and self.tier_budget.offload_tier is not None):
            self._tier_device_args = (hierarchy,
                                      self.tier_budget.offload_tier,
                                      hbs_gbps, hbs_latency_us)
        # chiplet promotion level (DESIGN.md SS17): when the budget's
        # leading tier is promotion-only (the hierarchy carries a chiplet
        # side tier), migrations over the bonded chiplet link are charged
        # on their own device with independent in/out queues
        self._chiplet_device_args = None
        if (hierarchy is not None and self.tier_budget is not None
                and self.tier_budget.n_promote):
            self._chiplet_device_args = (hierarchy,
                                         self.tier_budget.tiers[0][0],
                                         chiplet_gbps, chiplet_latency_us)
        # layer-sliced migration overlapped with the layer loop (SS17):
        # demand fetches become chained descriptors of n_layers slices
        # pipelined against per-layer compute; off -> the whole-block
        # barrier baseline (--no-layer-overlap)
        self.layer_overlap = layer_overlap
        self.n_layer_slices = layer_dma_slices(cfg) if layer_overlap else 1
        # requested pool size; PagedKVManager clamps it to the tier budget
        self.n_pages = (n_pages if n_pages is not None
                        else max_batch * self.n_pages_per_seq + 1)
        self._prefill_chunk = jax.jit(
            _program(prefill_paged_chunk, cfg, opts=opts),
            static_argnames=("calibrate",), donate_argnums=(2,))
        # fused K-step decode over the paged pool: sample + EOS-latch on
        # device, one host sync per (B, K) token block (DESIGN.md SS12)
        self._decode_fused = jax.jit(
            _program(decode_steps_paged, cfg, opts=opts, eos_id=eos_id,
                     temperature=temperature, top_k=top_k, top_p=top_p),
            static_argnames=("n_steps",), donate_argnums=(4,))
        # speculative verify: one paged multi-query pass scores the whole
        # draft window, leftover/rejection sampling accepts on device (SS14)
        self._spec_verify = jax.jit(
            _program(spec_decode_verify, cfg, opts=opts,
                     temperature=temperature, top_k=top_k, top_p=top_p),
            donate_argnums=(5,))
        # per-request sampling keys: fold (rid, tokens-emitted) into the
        # serve seed, so a request's randomness is independent of batch
        # composition and survives recompute preemption bit-for-bit
        _base = jax.random.PRNGKey(sample_seed)

        def block_keys(rids, emitted):
            def one(r, e):
                return jax.random.fold_in(jax.random.fold_in(_base, r), e)
            return jax.vmap(one)(rids, emitted)
        self._block_keys = jax.jit(block_keys)
        self._sample1 = jax.jit(_program(sampling.sample,
                                         temperature=temperature,
                                         top_k=top_k, top_p=top_p))
        self._copy_pages = jax.jit(_program(copy_pages, cfg),
                                   donate_argnums=(0,))
        self._chunk_shapes: set = set()   # distinct jitted prefill shapes
        self._decode_shapes: set = set()  # distinct jitted decode shapes
        self.kv_manager: Optional[PagedKVManager] = None  # set per serve()
        self.pool = None          # the last serve's device KV page pool
        # structured trace of the LAST serve_continuous run (SS15), plus
        # its reconcile report (trace audited against ServeStats deltas)
        self.trace: Optional[TraceRecorder] = None
        self.trace_report: Optional[dict] = None
        self.stats = ServeStats()

    # ------------------------------------------------------------------ #
    def generate(self, prompts, max_new_tokens: int, *, prefix_emb=None,
                 greedy: bool = True, seed: int = 0) -> List[List[int]]:
        """prompts: (B, S) int array (equal lengths per wave).

        Greedy decode runs through the fused K-step path (DESIGN.md SS12):
        the host pulls one (B, K) token block per sync instead of one
        token, with K = ``decode_lookahead``. Emitted columns are identical
        for every K — blocks may overrun the EOS stopping point on device,
        but the host truncates at exactly the step the per-token loop
        would have stopped at."""
        prompts = jnp.asarray(prompts, jnp.int32)
        B, S = prompts.shape
        pfx = prefix_emb.shape[1] if prefix_emb is not None else 0
        total = S + pfx + max_new_tokens
        assert total <= self.max_len, (
            f"prompt({S}) + prefix({pfx}) + new({max_new_tokens}) = {total} "
            f"exceeds max_len={self.max_len}")
        K = self.decode_lookahead if greedy else 1
        n_blocks = -(-max(max_new_tokens - 1, 0) // K)
        # the last fused block may overrun the token budget; headroom keeps
        # its (discarded) writes in-bounds instead of clamp-corrupting
        cache = init_cache(self.cfg, B, S + pfx + 1 + n_blocks * K,
                           self.opts)

        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, prompts, cache,
                                      prefix_emb=prefix_emb)
        logits.block_until_ready()
        self.stats.host_syncs += 1
        self.stats.prefill_s += time.perf_counter() - t0

        out: List[np.ndarray] = []
        done = np.zeros((B,), bool)
        t0 = time.perf_counter()
        launched = 0                        # device decode micro-steps
        if greedy:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            pending = tok[:, None]          # device columns not yet pulled
            n_sent = 1                      # tokens produced on device
            stop = False
            while True:
                cols = np.asarray(pending)
                self.stats.host_syncs += 1
                for j in range(cols.shape[1]):
                    if len(out) >= max_new_tokens:
                        break
                    out.append(cols[:, j])
                    if self.eos_id is not None:
                        done |= cols[:, j] == self.eos_id
                        if done.all():
                            stop = True
                            break
                if stop or len(out) >= max_new_tokens:
                    break
                # tail blocks run short (power-of-two clamp, O(log K)
                # compiled shapes) instead of overrunning the budget
                k_eff = min(K, _next_pow2(max_new_tokens - len(out)))
                self._decode_shapes.add(("dense", B, k_eff))
                pending, cache = self._decode_block(
                    self.params, tok, jnp.int32(S + pfx + n_sent - 1),
                    cache, n_steps=k_eff)
                tok = pending[:, -1]
                n_sent += k_eff
                launched += k_eff
        else:
            key = jax.random.PRNGKey(seed)
            for i in range(max_new_tokens):
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, logits).astype(jnp.int32)
                out.append(np.asarray(tok))
                self.stats.host_syncs += 1
                if self.eos_id is not None:
                    done |= out[-1] == self.eos_id
                    if done.all():
                        break
                if i + 1 < max_new_tokens:
                    logits, cache = self._decode(
                        self.params, tok, jnp.int32(S + pfx + i), cache)
                    launched += 1
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.new_tokens += len(out) * B
        self.stats.requests += B
        # launched device micro-steps (may exceed emitted-1: blocks can
        # overrun EOS) — the same semantics as the continuous engine
        self.stats.decode_steps += launched
        self.stats.decode_compiles = len(self._decode_shapes)
        seqs = np.stack(out, axis=1)
        return [row.tolist() for row in seqs]

    # ------------------------------------------------------------------ #
    def serve(self, requests: List[List[int]],
              max_new_tokens: int) -> List[List[int]]:
        """Serve ragged requests with the configured scheduler."""
        if self.scheduler == "continuous":
            return self.serve_continuous(requests, max_new_tokens)
        return self.serve_bucketed(requests, max_new_tokens)

    def serve_bucketed(self, requests: List[List[int]],
                       max_new_tokens: int) -> List[List[int]]:
        """Group ragged requests into equal-length waves and serve each."""
        buckets: Dict[int, List[int]] = {}
        for i, r in enumerate(requests):
            buckets.setdefault(len(r), []).append(i)
        results: Dict[int, List[int]] = {}
        for length, idxs in sorted(buckets.items()):
            wave = jnp.asarray([requests[i] for i in idxs], jnp.int32)
            outs = self.generate(wave, max_new_tokens)
            for i, o in zip(idxs, outs):
                results[i] = o
        return [results[i] for i in range(len(requests))]

    # ------------------------------------------------------------------ #
    def serve_continuous(self, requests: List[List[int]],
                         max_new_tokens: int) -> List[List[int]]:
        """Continuous batching over the paged, tiered, prefix-shared KV
        pool with chunked prefill (DESIGN.md SS10/SS11).

        Admissions do not monopolize the loop: each step spends at most
        ``prefill_budget`` tokens advancing PREFILLING slots by fixed-size
        chunks, then runs one fused ``decode_lookahead``-step decode block
        over the RUNNING slots (on-device sampling + EOS latch, KV pages
        reserved ahead all-or-nothing, one host sync per block; SS12).
        Prompts sharing an already-seen prefix skip both the recompute and
        the pages (refcounted reuse; COW on mid-page divergence)."""
        for i, r in enumerate(requests):
            total = len(r) + max_new_tokens
            if total > self.max_len:
                raise ValueError(f"request {i}: prompt({len(r)}) + "
                                 f"new({max_new_tokens}) exceeds "
                                 f"max_len={self.max_len}")
        # structured trace (SS15): one recorder per serve, threaded through
        # the scheduler / KV manager / tier device / drafter; ServeStats is
        # audited against it when the run finishes (reconcile). Host
        # phases tile the serve on the wall clock from here to reconcile.
        trace = TraceRecorder()
        self.trace = trace
        trace.phase("setup")
        try:
            return self._serve_paged(trace, requests, max_new_tokens)
        finally:
            trace.end_phases()   # after a failure: closes the open phase
                                 # and drops the compile listener

    def _serve_paged(self, trace: TraceRecorder, requests: List[List[int]],
                     max_new_tokens: int) -> List[List[int]]:
        """The body of ``serve_continuous``, recording into ``trace``."""
        ps, n_pp = self.page_size, self.n_pages_per_seq
        B = self.max_batch
        C = self.prefill_chunk
        # virtual stream clock (SS13/SS16), t = 0 at serve start: a
        # prefill worker and a decode worker, each an in-order
        # ``VirtualStream`` charging its ops' measured wall time plus any
        # absorbed migration stall to its own horizon. With overlap the
        # streams advance independently — chunked prefill of admitted
        # requests proceeds in virtual time while the fused decode block
        # of running requests is in flight — and the serve makespan is
        # ``max(free)``; without, both names bind one stream and every op
        # serializes (the pre-SS16 loop). TTFT/ITL/TPS, the trace and the
        # tier device's DMA horizons all read this clock.
        pstream = VirtualStream("prefill")
        dstream = VirtualStream("decode") if self.overlap else pstream
        # the prefill -> decode ready queue: rid -> virtual instant its
        # last prefill chunk finished (set at finish_prefill); a decode
        # block only includes requests ready by its start time
        decode_ready: Dict[int, float] = {}
        # a preemption victim's re-prefill cannot begin before the
        # (decode-stream) instant of the reservation that evicted it
        svc_floor: Dict[int, float] = {}
        # scheduler/drafter clock: admissions stamp at the lagging
        # stream's horizon (never later than any upcoming op start);
        # during a decode-side reservation the engine pins it to the
        # block's start so preemption instants land at eviction time
        sched_t = [0.0]

        def now() -> float:
            return max(sched_t[0], min(pstream.free, dstream.free))

        # stats accumulate across serve() calls but the trace covers only
        # this one — snapshot now, reconcile against the deltas
        snap_stall = self.stats.stall_s
        snap_ttft, snap_itl = len(self.stats.ttft), len(self.stats.itl)
        snap_tokens = self.stats.new_tokens
        snap_srid = dict(self.stats.stall_by_rid)
        device = (SimulatedTierDevice.from_hierarchy(
                      self._tier_device_args[0], self._tier_device_args[1],
                      bw_gbps=self._tier_device_args[2],
                      latency_us=self._tier_device_args[3],
                      duplex=(self.writeback_link == "dedicated"))
                  if self._tier_device_args is not None else None)
        if device is not None:
            device.tracer = trace
        # bonded chiplet link (SS17): its own device with independent
        # in/out queues — promotions/demotions never contend with the
        # offload link, and never gate a kernel
        cdev = (SimulatedTierDevice.from_hierarchy(
                    self._chiplet_device_args[0],
                    self._chiplet_device_args[1],
                    bw_gbps=self._chiplet_device_args[2],
                    latency_us=self._chiplet_device_args[3],
                    link="chiplet")
                if self._chiplet_device_args is not None else None)
        if cdev is not None:
            cdev.tracer = trace
        kv = PagedKVManager(self.n_pages, ps, tier_budget=self.tier_budget,
                            enable_prefix_cache=self.prefix_cache,
                            dtype_bytes=self.kv_dtype_bytes,
                            page_nbytes=self.page_nbytes_shard,
                            tier_device=device, chiplet_device=cdev,
                            tracer=trace)
        self.kv_manager = kv
        sched = ContinuousScheduler(kv, B, prefill_chunk=C,
                                    prefill_budget=self.prefill_budget,
                                    tracer=trace, clock=now)
        # draft proposer + acceptance-adaptive window sizing (SS14); fresh
        # per serve() so lookup indices / draft KV never leak across runs
        draft = adaptive = None
        if self.spec_mode == "ngram":
            from repro.serving.draft import NGramDraft
            draft = NGramDraft()
            adaptive = AdaptiveSpecK(self.spec_k)
        elif self.spec_mode == "model":
            from repro.serving.draft import ModelDraft
            draft = ModelDraft(self.draft_cfg, self.draft_params,
                               page_size=ps, max_batch=B,
                               max_len=self.max_len)
            self.draft_params = draft.params    # reuse across serve() calls
            adaptive = AdaptiveSpecK(self.spec_k)
        if draft is not None:
            draft.tracer, draft.clock = trace, now
        cache = init_paged_cache(self.cfg, kv.n_pages, ps, self.opts)
        if self.mesh is not None:
            # land the pool head-sharded up front so the jitted shard_map
            # callers never reshard it (the page scatter is elementwise on
            # the unsharded pages axis; GSPMD keeps the layout)
            from repro.sharding import rules
            from jax.sharding import NamedSharding
            cache = jax.device_put(cache, jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                rules.paged_cache_pspecs(cache, self.mesh)))
        calibrated = self.opts.cache_dtype != "int8"  # only int8 calibrates

        def stall_plan(reqs: List[Request], t0: float):
            """Pre-kernel half of the fetch-wait barrier (SS17): decide
            swaps/spills and charge write-back now, defer the demand-fetch
            issue until the kernel's wall time is known so the fetch can
            be layer-sliced against the layer loop."""
            with trace.layer("kv.residency"):
                return kv.plan_residency([r.rid for r in reqs], t0)

        def stall_charge(plan, reqs: List[Request], t0: float, dw: float,
                         track: str) -> float:
            """Post-kernel half: issue the planned fetch (layer-sliced
            when overlap is on), compute the pipelined stall, and
            attribute it — the batch absorbs the stall into the issuing
            stream's next op (the caller folds the return into the op's
            duration), each request is charged its OWN pages' wait scaled
            by the overlap savings (SS13/SS17)."""
            per: Dict[int, float] = {}
            with trace.layer("kv.residency"):
                s, barrier = kv.charge_residency(
                    plan, t0, n_slices=self.n_layer_slices, compute_s=dw,
                    per_seq=per)
            if s > 0:
                self.stats.stall_s += s
            self.stats.stall_saved_s += max(0.0, barrier - s)
            trace.absorbed_stall(t0, s, track=track)
            for r in reqs:
                v = per.get(r.rid, 0.0)
                if v > 0:
                    r.stall_s += v
                    self.stats.stall_by_rid[r.rid] = (
                        self.stats.stall_by_rid.get(r.rid, 0.0) + v)
                    trace.span(r.rid, STALL, t0, t0 + v)
            return s

        for i, r in enumerate(requests):
            req = Request(rid=i, prompt=list(r),
                          max_new_tokens=max_new_tokens)
            req.t_submit = now()
            trace.submit(req.rid, req.t_submit)
            sched.submit(req)

        def finished(req: Request, tok: int) -> bool:
            return (req.remaining <= 0
                    or (self.eos_id is not None and tok == self.eos_id))

        def emit(req: Request, tok: int, at: float) -> float:
            # ``at``: attributed emission time on the issuing stream —
            # fused blocks spread their span evenly over produced tokens
            if not req.out:                      # very first token: TTFT
                self.stats.ttft.append(at - req.t_submit)
            elif req.t_last:
                self.stats.itl.append(at - req.t_last)
            req.t_last = at
            req.out.append(tok)
            self.stats.new_tokens += 1
            trace.token(req.rid, at, tok)
            return at

        def note_peak():
            # snapshot the landed-page split whenever occupancy peaks —
            # prefill-time peaks included (a run may never decode, e.g.
            # every request finishing at its first token)
            if (self.tier_budget is not None
                    and kv.n_used >= self.stats.peak_pages_used):
                self.stats.kv_split_at_peak = kv.kv_tier_split()
            self.stats.peak_pages_used = max(self.stats.peak_pages_used,
                                             kv.n_used)
            self.stats.peak_fast_pages = max(self.stats.peak_fast_pages,
                                             kv.fast_pages_used)

        def apply_copies():
            nonlocal cache
            with trace.layer("kv.copies"):
                pairs = kv.drain_copies()
                if pairs:
                    # pad to a power-of-two batch with null-page self-copies
                    # so the jitted scatter sees O(log) distinct shapes, not
                    # one compile per COW-batch size
                    pairs = _pad_pow2(pairs, (0, 0))
                    cache = self._copy_pages(cache,
                                             jnp.asarray(pairs, jnp.int32))

        while sched.has_work:
            trace.phase("admit")
            with trace.layer("sched.admit"):
                admitted = sched.admit()
            if admitted:
                # start migrating any offload-resident cached-prefix pages
                # toward the fast tiers before their first prefill chunk
                kv.prefetch_seqs([r.rid for _, r in admitted], now())
            apply_copies()       # COW copies must land before any KV write

            # ---- prefill worker: chunked, bounded by the budget ---- #
            budget = sched.prefill_budget
            for slot, req in sched.prefilling():
                if budget < C:
                    break
                pf = req.prefill_tokens
                F = len(pf)
                while budget >= C and req.state == PREFILLING:
                    trace.phase("prefill.prep")
                    start = req.n_prefilled
                    n_real = min(C, F - start)
                    toks = np.zeros((1, C), np.int32)
                    toks[0, :n_real] = pf[start:start + n_real]
                    pt = kv.table_row(req.rid, n_pp)[None]
                    self._chunk_shapes.add(((1, C), not calibrated))
                    t0 = pstream.start(svc_floor.get(req.rid, 0.0))
                    # cached prefix pages may be offload-resident: plan
                    # their migration now, issue the fetch layer-sliced
                    # against the chunk's layer loop after the kernel's
                    # wall time is measured (SS17)
                    plan = stall_plan([req], t0)
                    args = (jnp.asarray(toks), cache, jnp.asarray(pt),
                            jnp.int32(start),
                            jnp.asarray([start + n_real], jnp.int32))
                    trace.phase("prefill.run", rid=req.rid, start=start,
                                n=n_real)
                    logits, cache = self._prefill_chunk(
                        self.params, *args, calibrate=not calibrated)
                    logits.block_until_ready()
                    self.stats.host_syncs += 1
                    dw = trace.phase_elapsed()
                    s = stall_charge(plan, [req], t0, dw, "prefill")
                    t1 = pstream.commit(t0, s + dw)
                    trace.engine_span(
                        "prefill_chunk", t0, t1,
                        {"rid": req.rid, "tokens": [start, start + n_real]},
                        track="prefill")
                    trace.phase("prefill.commit")
                    calibrated = True
                    self.stats.prefill_s += t1 - t0
                    # recompute/prefill split by the request's computed
                    # high-water mark (re-prefill after preemption)
                    trace.prefill_span(req.rid, t0, t1, start,
                                       start + n_real)
                    self.stats.prefill_tokens_computed += n_real
                    budget -= C
                    req.n_prefilled = start + n_real
                    kv.mark_written(req.rid, req.n_prefilled)
                    # index finished full pages right away so concurrent
                    # shared-prefix admissions hit them mid-prefill
                    kv.register_prefix(req.rid, pf,
                                       n_valid=req.n_prefilled)
                    if req.n_prefilled >= F:
                        sched.finish_prefill(slot)
                        decode_ready[req.rid] = t1   # decodable from t1
                        trace.phase("first_token")
                        if self.temperature > 0:
                            # first token of the request: sampled from the
                            # (rid, 0) key so it is schedule-independent
                            k1 = self._block_keys(
                                jnp.asarray([req.rid], jnp.int32),
                                jnp.zeros((1,), jnp.int32))
                            tok = int(np.asarray(self._sample1(
                                logits[:, F - 1 - start], k1))[0])
                        else:
                            tok = int(np.argmax(
                                np.asarray(logits[0, F - 1 - start])))
                        # the first-token pull is its own device->host
                        # round trip, after the chunk's barrier sync
                        self.stats.host_syncs += 1
                        t_e = emit(req, tok, t1)
                        if finished(req, tok):
                            sched.retire(slot)
                            trace.retire(req.rid, t_e)
                            if draft is not None:
                                draft.drop(req.rid)

            running = sched.running()
            note_peak()
            if not running:
                if sched.has_work:
                    continue     # prefills advance / admissions retry
                break

            # ---- decode worker: one block over the READY running slots.
            # The block starts no earlier than the earliest ready instant
            # (so at least one request always qualifies); requests whose
            # prefill finished after that sit the block out — an inactive
            # slot with zero quota, which the device neither samples nor
            # writes for, so sitting out delays a request's tokens
            # without changing them (per-slot determinism) — and join the
            # next block once the decode stream catches up. Serialized
            # (overlap=False), the shared stream's horizon is past every
            # ready instant and everyone always qualifies.
            t0 = dstream.start(min(decode_ready.get(r.rid, 0.0)
                                   for _, r in running))
            parts = [(s, r) for s, r in running
                     if decode_ready.get(r.rid, 0.0) <= t0]

            if self.spec_mode != "off":
                # ==== speculative decode block (DESIGN.md SS14) ==== #
                # draft proposes up to k tokens per request; ONE verify
                # pass streams weights+KV once and lands n_acc+1 tokens
                trace.phase("spec.propose")
                items = [(req, min(adaptive.k_for(req), req.remaining - 1))
                         for _, req in parts]
                props = draft.propose_all(items)
                # a model draft pulls its proposed block to the host; the
                # n-gram draft is host-only and reports zero
                self.stats.host_syncs += draft.take_host_syncs()
                td = dstream.commit(t0, trace.phase_elapsed())
                trace.engine_span("spec_propose", t0, td,
                                  {"n_seqs": len(items)}, track="decode")
                trace.phase("decode.reserve")
                for _, r in parts:
                    # the whole batch waits out the proposal pass
                    trace.span(r.rid, DRAFT, t0, td)
                # reserve draft_len+1 KV writes per slot, all-or-nothing;
                # LIFO preemption may evict ANY slot — diff the full table
                before = dict(sched.slots)
                sched_t[0] = td       # evictions stamp at reservation time
                with trace.layer("sched.reserve"):
                    for slot, req in parts:
                        if slot in sched.slots:
                            sched.reserve_lookahead(
                                slot, len(props.get(req.rid, ())) + 1)
                sched_t[0] = 0.0
                evicted = [r for s, r in before.items()
                           if s not in sched.slots]
                for r in evicted:
                    svc_floor[r.rid] = td
                self.stats.preemptions += len(evicted)
                parts = [(s, r) for s, r in parts
                         if s in sched.slots and r.state == RUNNING]
                apply_copies()
                note_peak()
                if not parts:
                    continue
                trace.phase("decode.prep")
                # clamp the verify window to the largest live draft,
                # rounded up to a power of two (O(log K) compiled shapes)
                max_dl = max(len(props.get(r.rid, ())) for _, r in parts)
                n_tok = min(self.spec_k + 1, _next_pow2(max_dl + 1))
                tokens = np.zeros((B, n_tok), np.int32)
                draft_len = np.zeros((B,), np.int32)
                seq_lens = np.zeros((B,), np.int32)
                tables = np.zeros((B, n_pp), np.int32)
                rids = np.zeros((B,), np.int32)
                emitted = np.zeros((B,), np.int32)
                for slot, req in parts:
                    pr = list(props.get(req.rid, ()))[:n_tok - 1]
                    tokens[slot, 0] = req.out[-1]
                    if pr:
                        tokens[slot, 1:1 + len(pr)] = pr
                    draft_len[slot] = len(pr)
                    seq_lens[slot] = kv.seq_len(req.rid)  # landed extent
                    tables[slot] = kv.table_row(req.rid, n_pp)
                    rids[slot] = req.rid
                    emitted[slot] = len(req.out)
                keys = self._block_keys(jnp.asarray(rids),
                                        jnp.asarray(emitted))
                self._decode_shapes.add(("spec", B, n_tok))
                tb = dstream.start()
                plan = stall_plan([r for _, r in parts], tb)
                args = (jnp.asarray(tokens), jnp.asarray(draft_len),
                        jnp.asarray(seq_lens), jnp.asarray(tables), cache,
                        keys)
                trace.phase("spec.run", n_tok=n_tok, n_seqs=len(parts))
                out, n_acc, _, cache = self._spec_verify(self.params, *args)
                out_np = np.asarray(out)
                nacc_np = np.asarray(n_acc)
                self.stats.host_syncs += 1
                dw = trace.phase_elapsed()
                s = stall_charge(plan, [r for _, r in parts], tb, dw,
                                 "decode")
                tv = dstream.commit(tb, s + dw)
                dt = tv - t0
                trace.engine_span("spec_verify", tb, tv,
                                  {"n_tok": n_tok, "n_seqs": len(parts)},
                                  track="decode")
                trace.phase("decode.emit")
                self.stats.decode_s += dt
                self.stats.decode_steps += 1    # one streaming pass
                self.stats.spec_blocks += 1

                # distribute: accepted prefix + correction/bonus token; the
                # pass wall time is attributed evenly over ACCEPTED tokens
                # (the whole point: ITL shrinks with acceptance); rejected
                # suffix pages roll back via commit_speculative
                for slot, req in parts:
                    dl = int(draft_len[slot])
                    acc = int(nacc_np[slot])
                    self.stats.draft_proposed += dl
                    self.stats.draft_accepted += acc
                    req.draft_proposed += dl
                    req.draft_accepted += acc
                    adaptive.update(req, dl, acc)
                    m = acc + 1
                    fin = False
                    n_written = 0
                    for j in range(m):
                        tok = int(out_np[slot, j])
                        n_written += 1
                        emit(req, tok, at=t0 + dt * (j + 1) / m)
                        if finished(req, tok):
                            fin = True
                            break
                    t_end = t0 + dt * (n_written / m)
                    trace.span(req.rid, DECODE, t0, t_end)
                    trace.instant("spec_commit", t_end, rid=req.rid,
                                  args={"proposed": dl, "accepted": acc})
                    kv.commit_speculative(req.rid, n_written)
                    if fin:
                        sched.retire(slot)
                        trace.retire(req.rid, t_end)
                        draft.drop(req.rid)
            else:
                # ---- reserve the block's KV writes up front (may
                # preempt): K lookahead writes per slot, all-or-nothing;
                # LIFO preemption may evict ANY slot, including a
                # just-admitted PREFILLING one — diff the full slot table
                trace.phase("decode.reserve")
                K = self.decode_lookahead
                before = dict(sched.slots)
                sched_t[0] = t0       # evictions stamp at the block start
                with trace.layer("sched.reserve"):
                    for slot, req in parts:
                        if slot in sched.slots:  # may have been preempted
                            sched.reserve_lookahead(slot,
                                                    min(K, req.remaining))
                sched_t[0] = 0.0
                evicted = [r for s, r in before.items()
                           if s not in sched.slots]
                for r in evicted:
                    svc_floor[r.rid] = t0
                self.stats.preemptions += len(evicted)
                parts = [(s, r) for s, r in parts
                         if s in sched.slots and r.state == RUNNING]
                apply_copies()   # COW from reservations lands pre-scan
                note_peak()
                if not parts:
                    continue

                trace.phase("decode.prep")
                # ---- one fused K-step decode block over the ready slots:
                # sampling, EOS latching, and length advance happen on
                # device; one host sync per (B, K) block (DESIGN.md SS12)
                tokens = np.zeros((B,), np.int32)
                seq_lens = np.zeros((B,), np.int32)
                tables = np.zeros((B, n_pp), np.int32)
                quota = np.zeros((B,), np.int32)
                inactive = np.ones((B,), bool)
                for slot, req in parts:
                    tokens[slot] = req.out[-1]
                    seq_lens[slot] = kv.seq_len(req.rid)  # write position
                    tables[slot] = kv.table_row(req.rid, n_pp)
                    quota[slot] = min(K, req.remaining)
                    inactive[slot] = False
                # clamp the block to the largest live quota, rounded up to
                # a power of two: a tail block (everyone nearly done) runs
                # short instead of decoding K wasted pad steps
                n_steps = min(K, _next_pow2(int(quota.max())))
                self._decode_shapes.add(("paged", B, n_steps))
                # fetch-wait barrier (SS13/SS17): every page this block
                # attends over must be fast-resident — or its layer slice
                # landed — before the layer consumes it; a block that
                # outruns its prefetch absorbs the residual as recorded
                # stall, shrunk by the layer-loop overlap
                plan = stall_plan([r for _, r in parts], t0)
                args = (jnp.asarray(tokens), jnp.asarray(seq_lens),
                        jnp.asarray(tables), cache)
                kw = dict(done=jnp.asarray(inactive),
                          quota=jnp.asarray(quota))
                if self.temperature > 0:
                    rids = np.zeros((B,), np.int32)
                    emitted = np.zeros((B,), np.int32)
                    for slot, req in parts:
                        rids[slot] = req.rid
                        emitted[slot] = len(req.out)
                    kw["keys"] = self._block_keys(jnp.asarray(rids),
                                                  jnp.asarray(emitted))
                trace.phase("decode.run", n_steps=n_steps,
                            n_seqs=len(parts))
                blk, cache, *_ = self._decode_fused(
                    self.params, *args, n_steps=n_steps, **kw)
                blk_np = np.asarray(blk)
                self.stats.host_syncs += 1
                dw = trace.phase_elapsed()
                s = stall_charge(plan, [r for _, r in parts], t0, dw,
                                 "decode")
                tv = dstream.commit(t0, s + dw)
                dt = tv - t0
                trace.engine_span("decode_block", t0, tv,
                                  {"n_steps": n_steps,
                                   "n_seqs": len(parts)}, track="decode")
                trace.phase("decode.emit")
                self.stats.decode_s += dt
                self.stats.decode_steps += n_steps

                # distribute the block: per-token ITL is attributed evenly
                # from the block wall time; retire/commit at boundaries
                for slot, req in parts:
                    fin = False
                    n_written = 0            # device-side KV writes taken
                    for j in range(int(quota[slot])):
                        tok = int(blk_np[slot, j])
                        n_written += 1
                        emit(req, tok, at=t0 + dt * (j + 1) / n_steps)
                        if finished(req, tok):
                            fin = True
                            break
                    t_end = t0 + dt * (n_written / n_steps)
                    trace.span(req.rid, DECODE, t0, t_end)
                    kv.commit_tokens(req.rid, n_written)
                    if fin:
                        sched.retire(slot)   # frees surplus reserved pages
                        trace.retire(req.rid, t_end)

            # prefetch AHEAD of the next block, backdated to this block's
            # launch: the next block reads the same sequences' pages, so
            # any of them demoted to (or streamed from) the offload tier
            # migrates while this block was computing — at generous HBS
            # bandwidth the next barrier then sees zero stall. When the
            # fetch channel would otherwise sit idle, the lookahead arg
            # additionally promotes the deepest still-prefilling
            # sequence's pages (queue-aware prefetch, ROADMAP item 5)
            cont = [r.rid for s, r in running if s in sched.slots]
            if cont:
                kv.prefetch_seqs(cont, t0, lookahead_seqs=[
                    r.rid for _, r in sched.prefilling()])

        trace.phase("finish")
        self.stats.requests += len(requests)
        self.stats.cached_prefix_tokens += kv.dedup_tokens
        self.stats.pages_deduped += kv.dedup_hits
        self.stats.cow_copies += kv.cow_copies
        self.stats.spill_bytes += kv.spill_bytes
        self.stats.fetch_bytes += kv.fetch_bytes
        self.stats.pages_spilled += kv.n_spills
        self.stats.pages_fetched += kv.n_fetches
        self.stats.prefetch_hits += kv.prefetch_hits
        self.stats.prefetch_misses += kv.prefetch_misses
        self.stats.clean_demotions += kv.clean_demotions
        self.stats.chiplet_promotions += kv.chiplet_promotions
        self.stats.chiplet_demotions += kv.chiplet_demotions
        for ch, nb in kv.channel_bytes.items():
            self.stats.channel_bytes[ch] = (
                self.stats.channel_bytes.get(ch, 0.0) + nb)
        for tier, n in kv.tier_touches.items():
            self.stats.tier_touches[tier] = (
                self.stats.tier_touches.get(tier, 0) + n)
        self.pool = cache
        self.stats.prefill_compiles = len(self._chunk_shapes)
        self.stats.decode_compiles = len(self._decode_shapes)
        assert not sched.waiting and not sched.slots, "unserved requests"
        assert kv.n_used == 0, "page leak: retired sequences kept pages"
        # serve makespan: the later stream's horizon (== the serialized
        # sum when overlap is off; less when prefill hid behind decode)
        self.stats.serve_s += max(pstream.free, dstream.free)
        # close the trace and audit the aggregate counters against it:
        # phase sums == e2e per request, stall totals and samples match
        # this serve's ServeStats deltas, host phases tile the wall time
        # (raises on drift — SS15)
        trace.finalize(max(pstream.free, dstream.free))
        self.trace_report = trace.reconcile(
            stall_s=self.stats.stall_s - snap_stall,
            ttft=self.stats.ttft[snap_ttft:],
            itl=self.stats.itl[snap_itl:],
            new_tokens=self.stats.new_tokens - snap_tokens,
            stall_by_rid={rid: v - snap_srid.get(rid, 0.0)
                          for rid, v in self.stats.stall_by_rid.items()},
            channel_bytes=dict(kv.channel_bytes))
        by_rid = {req.rid: req.out for req in sched.done}
        return [by_rid[i] for i in range(len(requests))]
