"""Shared-prefix KV page reuse + chunked prefill (DESIGN.md SS11).

Covers the chunk-prefill kernel vs its jnp oracle, manager refcount /
COW / eviction invariants (incl. a hypothesis property test), chunked
scheduling, and engine-level equivalence: prefix cache on vs off is
token-identical under the native kv_policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.decode_attention as da
import repro.kernels.ref as ref
from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.core import kv_dedup_factor, max_concurrency_without_spill
from repro.models import RuntimeOptions, init_params
from repro.serving import (ContinuousScheduler, PageAllocationError,
                           PagedKVManager, Request, ServeEngine)
from repro.serving.scheduler import PREFILLING, RUNNING


# ----------------------- chunk-prefill kernel -------------------------- #

@pytest.mark.parametrize("B,H,Hkv,dh,ps,C,start,real", [
    (1, 8, 2, 64, 16, 32, 0, 32),      # first chunk, GQA
    (1, 4, 1, 128, 16, 32, 32, 20),    # later chunk with right-padding, MQA
    (2, 4, 4, 64, 8, 16, 8, 16),       # MHA, mid-page grid skipping
])
def test_chunk_kernel_matches_oracle(B, H, Hkv, dh, ps, C, start, real):
    """Acceptance: the chunk-prefill Pallas kernel matches the jnp oracle
    in interpret mode."""
    npp = (start + C) // ps + 2
    P = B * npp + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, C, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, ps, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, ps, dh), jnp.float32)
    perm = np.asarray(jax.random.permutation(ks[0], P - 1)) + 1
    pt = jnp.asarray(perm[:B * npp].reshape(B, npp), jnp.int32)
    nv = jnp.full((B,), start + real, jnp.int32)
    out = da.chunk_prefill_attention(q, kp, vp, pt, start, nv,
                                     interpret=True)
    want = ref.chunk_prefill_attention_ref(q, kp, vp, pt, start, nv,
                                           scale=dh ** -0.5)
    np.testing.assert_allclose(out[:, :real], want[:, :real],
                               atol=1e-5, rtol=1e-5)


def test_chunk_kernel_int8():
    """Acceptance: int8 path within quantization tolerance of the fp ref."""
    B, C, H, Hkv, dh, ps, npp = 1, 16, 8, 2, 64, 32, 3
    P = npp + 2
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, C, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, ps, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, ps, dh), jnp.float32)
    pt = jnp.asarray([[2, 3, 1]], jnp.int32)
    start, nv = 32, jnp.asarray([48], jnp.int32)
    ki, vi, ksc, vsc = da.quantize_kv(kp, vp, head_axis=1)
    out = da.chunk_prefill_attention(q, ki, vi, pt, start, nv, k_scale=ksc,
                                     v_scale=vsc, interpret=True)
    want = ref.chunk_prefill_attention_ref(q, ki, vi, pt, start, nv,
                                           scale=dh ** -0.5,
                                           k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    fp = ref.chunk_prefill_attention_ref(q, kp, vp, pt, start, nv,
                                         scale=dh ** -0.5)
    assert float(jnp.max(jnp.abs(out - fp))) < 0.05


@pytest.mark.parametrize("L,block_kv", [(100, 64), (97, 512), (130, 128)])
def test_decode_attention_non_multiple_block(L, block_kv):
    """Satellite: L not a multiple of block_kv no longer crashes — the KV
    tail is padded (and masked), keeping lane-aligned blocks even for
    prime L."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 4, 64), jnp.float32)
    kc = jax.random.normal(ks[1], (2, L, 2, 64), jnp.float32)
    vc = jax.random.normal(ks[2], (2, L, 2, 64), jnp.float32)
    lens = jnp.asarray([7, L], jnp.int32)
    out = da.decode_attention(q, kc, vc, lens, block_kv=block_kv,
                              interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lens, scale=64 ** -0.5)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


# ------------------------- manager: refcounts -------------------------- #

def _brute_used(kv):
    return len({p for sid in list(kv._seqs) for p in kv.seq_pages(sid)})


def _pool_ok(kv):
    assert kv.n_free + kv.n_evictable + kv.n_used == kv.n_pages - 1
    assert kv.n_used == _brute_used(kv)        # O(1) counter stays exact
    for sid in list(kv._seqs):
        for p in kv.seq_pages(sid):
            assert kv.page_ref(p) >= 1


def test_refcounted_sharing_and_eviction():
    kv = PagedKVManager(n_pages=16, page_size=4, enable_prefix_cache=True)
    doc = list(range(100, 112))                    # 3 full pages
    a = kv.allocate_shared(0, doc + [1, 2], reserve_tokens=16)
    assert a.n_cached == 0 and kv.n_used == 4
    kv.register_prefix(0, doc + [1, 2])            # indexes the 3 doc pages
    b = kv.allocate_shared(1, doc + [7, 8], reserve_tokens=16)
    assert b.n_cached == 12                        # full-page reuse
    assert b.pages[:3] == a.pages[:3]
    assert all(kv.page_ref(p) == 2 for p in a.pages[:3])
    assert kv.n_used == 5                          # 3 shared + 2 private
    _pool_ok(kv)

    kv.free_seq(0)                   # shared pages still held by seq 1
    assert kv.n_evictable == 0 and kv.n_used == 4
    _pool_ok(kv)
    kv.free_seq(1)                   # cached doc pages become evictable
    assert kv.n_evictable == 3 and kv.n_used == 0
    _pool_ok(kv)
    c = kv.allocate_shared(2, doc + [9], reserve_tokens=16)
    assert c.n_cached == 12                        # revived from evictable
    assert kv.n_evictable == 0 and kv.page_ref(c.pages[0]) == 1
    _pool_ok(kv)

    kv.free_seq(2)
    assert kv.n_used == 0 and kv.n_evictable == 3  # doc stays cached
    # pressure reclaims evictable pages LRU (no leak, index dropped)
    kv.allocate(9, 15 * 4)                         # whole pool
    assert kv.n_evictable == 0 and kv.evictions == 3
    assert not kv._index
    _pool_ok(kv)


def test_cow_on_shared_page_write():
    kv = PagedKVManager(n_pages=12, page_size=4, enable_prefix_cache=True)
    doc = list(range(50, 58))                      # 2 full pages
    kv.allocate_shared(0, doc + [1])
    kv.register_prefix(0, doc + [1])
    kv.allocate_shared(1, doc + [2])
    shared = kv.seq_pages(0)[0]
    assert kv.page_ref(shared) == 2
    # seq 1 must not write into the shared page in place
    pair = kv.ensure_writable(1, 0)
    assert pair is not None and pair[0] == shared
    assert kv.seq_pages(1)[0] == pair[1] != shared
    assert kv.page_ref(shared) == 1 and kv.page_ref(pair[1]) == 1
    assert kv.seq_pages(0)[0] == shared            # owner untouched
    assert kv.drain_copies() == [pair]
    _pool_ok(kv)
    # exclusive-but-cached page: unregistered instead of copied
    assert kv.ensure_writable(0, 0) is None
    assert not kv.is_cached(kv.seq_pages(0)[0])
    _pool_ok(kv)


def test_partial_page_cow_match():
    kv = PagedKVManager(n_pages=12, page_size=4, enable_prefix_cache=True)
    donor = [9, 9, 9, 9, 5, 6, 7, 8]               # 2 full pages
    kv.allocate_shared(0, donor + [1])
    kv.register_prefix(0, donor + [1])
    # matches page 0 fully, page 1 up to 2 tokens -> COW of page 1
    req = [9, 9, 9, 9, 5, 6, 70, 80, 3]
    b = kv.allocate_shared(1, req)
    assert b.n_cached == 6 and kv.cow_copies == 1
    src_dst = kv.drain_copies()
    assert src_dst == [(kv.seq_pages(0)[1], kv.seq_pages(1)[1])]
    assert kv.seq_pages(1)[0] == kv.seq_pages(0)[0]     # full page shared
    assert kv.seq_pages(1)[1] != kv.seq_pages(0)[1]     # partial is private
    _pool_ok(kv)


def test_identical_prompt_caps_last_token():
    """A fully-cached prompt still recomputes its final token (partial COW
    of the last page when the divergence is mid-page)."""
    kv = PagedKVManager(n_pages=12, page_size=4, enable_prefix_cache=True)
    p = list(range(30, 38))                        # exactly 2 pages
    kv.allocate_shared(0, p)
    kv.register_prefix(0, p, n_valid=8)
    b = kv.allocate_shared(1, p)
    assert b.n_cached == 7                         # 1 full page + 3 via COW
    assert kv.cow_copies == 1
    _pool_ok(kv)


def test_append_token_into_shared_page_cows():
    kv = PagedKVManager(n_pages=12, page_size=4, enable_prefix_cache=True)
    kv.allocate(0, 6)                              # 2 pages, 6 tokens
    kv.register_prefix(0, list(range(6)), n_valid=4)
    kv.allocate_shared(1, list(range(6)))          # shares page 0
    # force seq 1's tracked length onto the shared page boundary write
    last = kv.seq_pages(0)[0]
    kv._seqs[1].pages[1] = kv._seqs[1].pages[1]    # (layout unchanged)
    kv._seqs[1].n_tokens = 3                       # next write -> page 0
    before = kv.seq_pages(1)[0]
    assert kv.page_ref(before) == 2
    kv.append_token(1)
    after = kv.seq_pages(1)[0]
    assert after != before and kv.page_ref(before) == 1
    assert kv.drain_copies() == [(before, after)]
    assert last == before
    _pool_ok(kv)


def test_hypothesis_refcounted_pool_never_leaks():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    ops = st.lists(st.tuples(st.integers(0, 4), st.integers(0, 7),
                             st.integers(1, 30)), min_size=1, max_size=60)

    @settings(max_examples=60, deadline=None)
    @given(ops=ops, data=st.data())
    def run(ops, data):
        kv = PagedKVManager(n_pages=12, page_size=4,
                            enable_prefix_cache=True)
        for kind, sid, n in ops:
            alive = sid in kv._seqs
            try:
                if kind == 0 and not alive:
                    # tiny alphabet -> frequent shared prefixes
                    toks = data.draw(st.lists(st.integers(1, 3),
                                              min_size=1, max_size=20))
                    kv.allocate_shared(sid, toks)
                    kv._tokens = getattr(kv, "_tokens", {})
                    kv._tokens[sid] = toks
                elif kind == 1 and alive:
                    kv.append_token(sid)
                    kv._tokens[sid].append(data.draw(st.integers(1, 3)))
                elif kind == 2 and alive:
                    kv.register_prefix(sid, kv._tokens[sid])
                elif kind == 3 and alive:
                    kv.free_seq(sid)
                elif kind == 4 and alive:
                    kv.ensure_writable(sid, n % kv.seq_len(sid))
            except PageAllocationError:
                pass
            _pool_ok(kv)
        for sid in list(kv._seqs):
            kv.free_seq(sid)
        assert kv.n_used == 0                      # no leak, no double-free
        assert kv.n_free + kv.n_evictable == kv.n_pages - 1

    run()


# ------------------------ scheduler: chunking -------------------------- #

def test_scheduler_chunked_admit_and_budget():
    kv = PagedKVManager(64, 4, enable_prefix_cache=True)
    sched = ContinuousScheduler(kv, 4, prefill_chunk=8, prefill_budget=8)
    sched.submit(Request(rid=0, prompt=list(range(1, 20)), max_new_tokens=4))
    (slot, req), = sched.admit()
    assert req.state == PREFILLING and req.n_prefilled == 0
    assert sched.prefilling() == [(slot, req)]
    assert not sched.running()
    sched.finish_prefill(slot)
    assert req.state == RUNNING and sched.running() == [(slot, req)]
    with pytest.raises(ValueError):
        ContinuousScheduler(kv, 4, prefill_chunk=8, prefill_budget=4)


def test_scheduler_defers_shared_prefix_admission():
    kv = PagedKVManager(64, 4, enable_prefix_cache=True)
    sched = ContinuousScheduler(kv, 4, prefill_chunk=8)
    doc = [7] * 12
    a = Request(rid=0, prompt=doc + [1], max_new_tokens=2)
    b = Request(rid=1, prompt=doc + [2], max_new_tokens=2)
    sched.submit(a)
    sched.submit(b)
    assert len(sched.admit()) == 1                 # b waits for a's prefill
    assert sched.waiting and sched.waiting[0] is b
    a.n_prefilled = 13
    kv.register_prefix(0, a.prefill_tokens, n_valid=13)
    sched.finish_prefill(0)
    admitted = sched.admit()                       # prefix cached -> join
    assert len(admitted) == 1 and admitted[0][1] is b
    assert b.n_prefilled == 12                     # hit the 3 doc pages


# ----------------------- engine: end-to-end ---------------------------- #

@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("llama3.2-1b"), d_model=64, n_layers=2,
                  vocab=128)
    opts = RuntimeOptions(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), opts)
    return cfg, opts, params


def _shared_reqs(cfg, n=4, doc_len=17, q_len=4, seed=2):
    rng = np.random.default_rng(seed)
    doc = rng.integers(1, cfg.vocab, size=doc_len).tolist()
    return [doc + rng.integers(1, cfg.vocab, size=q_len).tolist()
            for _ in range(n)]


def test_prefix_cache_token_identical(small_model):
    """Acceptance: with kv_policy='native', outputs are token-identical
    with the prefix cache on vs off — and match the static engine."""
    cfg, opts, params = small_model
    reqs = _shared_reqs(cfg)
    want = ServeEngine(cfg, params, opts, max_len=40).serve(
        [r[:] for r in reqs], 6)
    outs, stats = {}, {}
    for pc in (False, True):
        eng = ServeEngine(cfg, params, opts, max_len=40,
                          scheduler="continuous", page_size=4, max_batch=4,
                          prefix_cache=pc, prefill_chunk=8)
        outs[pc] = eng.serve([r[:] for r in reqs], 6)
        stats[pc] = eng.stats
        assert eng.kv_manager.n_used == 0
    assert outs[False] == outs[True] == want
    # acceptance: >=30% fewer prefill tokens and fewer resident pages
    base = stats[False].prefill_tokens_computed
    assert stats[True].prefill_tokens_computed <= 0.7 * base
    assert stats[True].peak_pages_used < stats[False].peak_pages_used
    assert stats[True].pages_deduped > 0


@pytest.mark.slow
def test_cow_divergence_token_identical(small_model):
    """Mid-page divergence goes through COW and stays correct."""
    cfg, opts, params = small_model
    rng = np.random.default_rng(5)
    doc = rng.integers(1, cfg.vocab, size=12).tolist()
    reqs = [doc[:10], doc[:9] + [99, 98, 97]]      # diverge mid page (ps=4)
    want = ServeEngine(cfg, params, opts, max_len=40).serve(
        [r[:] for r in reqs], 6)
    eng = ServeEngine(cfg, params, opts, max_len=40, scheduler="continuous",
                      page_size=4, max_batch=1, prefix_cache=True,
                      prefill_chunk=8)
    assert eng.serve([r[:] for r in reqs], 6) == want
    assert eng.stats.cow_copies >= 1
    assert eng.stats.cached_prefix_tokens >= 9


def test_identical_prompts_share_all_but_last(small_model):
    cfg, opts, params = small_model
    rng = np.random.default_rng(6)
    p = rng.integers(1, cfg.vocab, size=16).tolist()
    reqs = [p[:] for _ in range(3)]
    want = ServeEngine(cfg, params, opts, max_len=40).serve(
        [r[:] for r in reqs], 6)
    eng = ServeEngine(cfg, params, opts, max_len=40, scheduler="continuous",
                      page_size=4, max_batch=4, prefix_cache=True,
                      prefill_chunk=8)
    assert eng.serve([r[:] for r in reqs], 6) == want
    assert eng.stats.cached_prefix_tokens == 2 * 15  # all but the last token


@pytest.mark.slow
def test_preempt_readmit_hits_cache(small_model):
    """A preemption victim's registered pages serve its own re-admission."""
    cfg, opts, params = small_model
    rng = np.random.default_rng(7)
    reqs = [rng.integers(1, cfg.vocab, size=8).tolist() for _ in range(2)]
    want = ServeEngine(cfg, params, opts, max_len=32).serve(
        [r[:] for r in reqs], 12)
    eng = ServeEngine(cfg, params, opts, max_len=32, scheduler="continuous",
                      page_size=4, max_batch=2, n_pages=8,
                      prefix_cache=True, prefill_chunk=8)
    assert eng.serve([r[:] for r in reqs], 12) == want
    assert eng.stats.preemptions >= 1
    assert eng.stats.cached_prefix_tokens > 0      # re-admit reused pages


@pytest.mark.slow
def test_chunked_prefill_compiles_once(small_model):
    """Acceptance: one jitted prefill for many distinct prompt lengths."""
    cfg, opts, params = small_model
    rng = np.random.default_rng(8)
    reqs = [rng.integers(1, cfg.vocab, size=n).tolist()
            for n in (3, 5, 7, 9, 11, 13, 17, 21)]
    want = ServeEngine(cfg, params, opts, max_len=32).serve(
        [r[:] for r in reqs], 4)
    eng = ServeEngine(cfg, params, opts, max_len=32, scheduler="continuous",
                      page_size=8, max_batch=4, prefix_cache=False)
    assert eng.serve([r[:] for r in reqs], 4) == want
    assert eng.stats.prefill_compiles == 1


def test_chunked_prefill_interleaves_decode(small_model):
    """A long admission must not stall in-flight decodes: decode steps run
    between its chunks (the prefill budget bounds per-step prefill work)."""
    cfg, opts, params = small_model
    rng = np.random.default_rng(9)
    short = rng.integers(1, cfg.vocab, size=4).tolist()
    long = rng.integers(1, cfg.vocab, size=24).tolist()
    eng = ServeEngine(cfg, params, opts, max_len=40, scheduler="continuous",
                      page_size=4, max_batch=2, prefix_cache=False,
                      prefill_chunk=8, prefill_budget=8)
    want = ServeEngine(cfg, params, opts, max_len=40).serve(
        [short[:], long[:]], 8)
    assert eng.serve([short[:], long[:]], 8) == want
    # the 24-token prompt takes 3 chunks; the short request decodes during
    # them, so decode steps exceed what a post-prefill-only schedule needs
    assert eng.stats.decode_steps >= 8


def test_stats_percentiles(small_model):
    cfg, opts, params = small_model
    reqs = _shared_reqs(cfg, n=3)
    eng = ServeEngine(cfg, params, opts, max_len=40, scheduler="continuous",
                      page_size=8, max_batch=4)
    eng.serve([r[:] for r in reqs], 6)
    s = eng.stats
    assert len(s.ttft) == 3 and len(s.itl) == 3 * 5
    assert s.ttft_p95 >= s.ttft_p50 > 0
    assert s.itl_p95 >= s.itl_p50 > 0


# ---------------------- analytical sharing model ----------------------- #

def test_kv_dedup_factor():
    assert kv_dedup_factor(8, 1000, 0, shared_prefix_len=0) == 1.0
    assert kv_dedup_factor(8, 1000, 0, share_group=1,
                           shared_prefix_len=500) == 1.0
    f = kv_dedup_factor(8, 1000, 0, shared_prefix_len=1000, share_group=8)
    assert f == pytest.approx(1 / 8)
    # monotone in the share factor
    fs = [kv_dedup_factor(8, 1000, 200, shared_prefix_len=800, share_group=g)
          for g in (1, 2, 4, 8)]
    assert fs == sorted(fs, reverse=True) and fs[0] == 1.0


def test_sharing_raises_no_spill_concurrency():
    """Acceptance: predicted max concurrency before spill increases with
    the share factor."""
    from repro.core import hbs, lpddr6, npu_hierarchy, qkv_in_ddr
    cfg = get_config("llama3.2-1b")
    hier = npu_hierarchy(lpddr6(520.0, capacity_gb=2.0),
                         hbs(64.0, latency_us=20.0))
    place = qkv_in_ddr()
    lims = [max_concurrency_without_spill(
        cfg, hier, place, prefill_len=2048, decode_len=256,
        shared_prefix_len=1536, share_group=g) for g in (1, 2, 4, 8)]
    assert lims == sorted(lims)
    assert lims[-1] > lims[0]