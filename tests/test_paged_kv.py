"""Paged KV cache: kernel equivalence, manager/scheduler invariants, and
continuous-vs-static engine equivalence (DESIGN.md SS10)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.decode_attention as da
import repro.kernels.ref as ref
from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.models import RuntimeOptions, init_params
from repro.serving import (ContinuousScheduler, PageAllocationError,
                           PagedKVManager, Request, ServeEngine, TierBudget)


# --------------------------- kernel equivalence ------------------------ #

def _mk_pages(key, P, ps, Hkv, dh, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    kp = jax.random.normal(ks[0], (P, Hkv, ps, dh), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[1], (P, Hkv, ps, dh), jnp.float32).astype(dtype)
    return kp, vp


def _disjoint_tables(key, B, npp, P):
    """Each sequence owns distinct pages (ids >= 1, page 0 reserved)."""
    perm = np.asarray(jax.random.permutation(key, P - 1)) + 1
    return jnp.asarray(perm[:B * npp].reshape(B, npp), jnp.int32)


@pytest.mark.parametrize("B,H,Hkv,dh,ps,npp", [
    (3, 8, 2, 64, 16, 8),        # GQA 4:1
    (2, 4, 1, 128, 32, 4),       # MQA
    (4, 4, 4, 64, 8, 6),         # MHA, small pages
])
def test_paged_matches_dense_kernel_ragged(B, H, Hkv, dh, ps, npp):
    """Acceptance: paged == dense kernel to <=1e-5 (f32) on ragged batches."""
    P = B * npp + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    kp, vp = _mk_pages(ks[1], P, ps, Hkv, dh)
    pt = _disjoint_tables(ks[2], B, npp, P)
    L = npp * ps
    lens = jax.random.randint(ks[3], (B,), 1, L + 1)

    paged = da.paged_decode_attention(q, kp, vp, pt, lens, interpret=True)
    # dense kernel over the gathered cache must agree
    kd = ref.gather_pages(kp, pt)
    vd = ref.gather_pages(vp, pt)
    dense = da.decode_attention(q, kd, vd, lens, interpret=True,
                                block_kv=min(512, L))
    np.testing.assert_allclose(paged, dense, atol=1e-5, rtol=1e-5)
    want = ref.paged_decode_attention_ref(q, kp, vp, pt, lens,
                                          scale=dh ** -0.5)
    np.testing.assert_allclose(paged, want, atol=1e-5, rtol=1e-5)


def test_paged_kernel_int8():
    B, H, Hkv, dh, ps, npp = 2, 8, 2, 64, 16, 6
    P = B * npp + 1
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    kp, vp = _mk_pages(ks[1], P, ps, Hkv, dh)
    pt = _disjoint_tables(ks[2], B, npp, P)
    lens = jnp.array([5, 90], jnp.int32)
    ki, vi, ksc, vsc = da.quantize_kv(kp, vp, head_axis=1)
    out = da.paged_decode_attention(q, ki, vi, pt, lens, k_scale=ksc,
                                    v_scale=vsc, interpret=True)
    want = ref.paged_decode_attention_ref(q, ki, vi, pt, lens,
                                          scale=dh ** -0.5,
                                          k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    # tracks the unquantized reference within quantization error
    fp = ref.paged_decode_attention_ref(q, kp, vp, pt, lens, scale=dh ** -0.5)
    assert float(jnp.max(jnp.abs(out - fp))) < 0.05


def test_paged_kernel_ignores_unowned_pages():
    """Pages outside the table — and table slots past seq_len — are inert."""
    B, H, dh, ps, npp = 1, 4, 64, 8, 4
    P = 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    kp, vp = _mk_pages(ks[1], P, ps, H, dh)
    pt = jnp.asarray([[3, 5, 0, 0]], jnp.int32)      # 2 real + null padding
    lens = jnp.array([13], jnp.int32)
    out1 = da.paged_decode_attention(q, kp, vp, pt, lens, interpret=True)
    owned = {3, 5}
    mask = np.ones((P,), bool)
    mask[list(owned)] = False
    kp2 = kp.at[mask].set(999.0)
    vp2 = vp.at[mask].set(-999.0)
    # also poison the owned-but-invalid tail of page 5 (rows 13..16)
    kp2 = kp2.at[5, :, 5:].set(777.0)
    out2 = da.paged_decode_attention(q, kp2, vp2, pt, lens, interpret=True)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


# ----------------------------- manager --------------------------------- #

def test_manager_alloc_free_invariants():
    kv = PagedKVManager(n_pages=10, page_size=4)
    assert kv.n_free == 9 and kv.n_used == 0           # page 0 reserved
    pages = kv.allocate(0, 9, reserve_tokens=12)       # 3 pages
    assert len(pages) == 3 and 0 not in pages
    assert kv.n_free == 6 and kv.n_used == 3
    kv.allocate(1, 4)
    with pytest.raises(ValueError):
        kv.allocate(1, 4)                              # double alloc
    # growth: 9 -> 12 tokens fit the reserve; 13th crosses a boundary
    for _ in range(3):
        assert kv.append_token(0) is None
    assert kv.append_token(0) is not None
    assert kv.n_used == 5
    assert kv.free_seq(0) == 4
    assert kv.free_seq(1) == 1
    assert kv.n_free == 9 and kv.n_used == 0           # no leak


def test_manager_exhaustion_raises():
    kv = PagedKVManager(n_pages=4, page_size=4)
    kv.allocate(0, 12)                                 # all 3 usable pages
    with pytest.raises(PageAllocationError):
        kv.allocate(1, 1)
    with pytest.raises(PageAllocationError):
        kv.append_token(0)
    assert not kv.can_admit(1)
    assert kv.fits_at_all(12) and not kv.fits_at_all(13)


def test_manager_table_row_pads_with_null_page():
    kv = PagedKVManager(n_pages=8, page_size=4)
    kv.allocate(7, 8)
    row = kv.table_row(7, 5)
    assert row.shape == (5,) and (row[2:] == 0).all() and (row[:2] > 0).all()


def test_tier_budget_and_split():
    from repro.core import hbs, lpddr6, npu_hierarchy, sram_chiplet
    from repro.serving.kv_manager import page_bytes

    cfg = reduced(get_config("llama3.2-1b"), d_model=64, n_layers=2)
    hier = npu_hierarchy(lpddr6(capacity_gb=1e-3),    # 1 MB "DDR"
                         hbs(64.0, latency_us=20.0, capacity_gb=1e-2),
                         chiplet=sram_chiplet(512.0, capacity_mb=0.1))
    pb = page_bytes(cfg, 16, 4)
    tb = TierBudget.from_hierarchy(hier, cfg, 16, 4)
    names = [n for n, _ in tb.tiers]
    assert names == ["chiplet", "ddr", "hbs"]          # fast tier first
    assert dict(tb.tiers)["chiplet"] == int(0.1e6 // pb)
    assert dict(tb.tiers)["ddr"] == int(1e6 // pb)

    # the chiplet is a promote-only level (SS17): fresh allocations land
    # in the base tiers and the chiplet fills by promotion, never by
    # first-touch assignment
    assert tb.n_promote == 1
    assert tb.promote_tiers == tb.tiers[:1]
    assert tb.offload_tier == "hbs"

    kv = PagedKVManager(n_pages=10_000, page_size=16, tier_budget=tb)
    assert kv.n_pages == tb.total_pages + 1            # budget caps the pool
    n_chip = dict(tb.tiers)["chiplet"]
    kv.allocate(0, (n_chip + 3) * 16)      # would have overflowed the chiplet
    split = kv.kv_tier_split()
    assert [s[0] for s in split] == ["ddr"]            # chiplet stays empty
    assert abs(sum(f for _, f in split) - 1.0) < 1e-9


# ---------------------------- scheduler -------------------------------- #

def _sched(n_pages=32, page_size=4, max_batch=4):
    kv = PagedKVManager(n_pages, page_size)
    return ContinuousScheduler(kv, max_batch), kv


def test_scheduler_admit_retire_no_leak():
    sched, kv = _sched()
    for i in range(6):
        sched.submit(Request(rid=i, prompt=[1] * 5, max_new_tokens=4))
    admitted = sched.admit()
    assert len(admitted) == 4                          # slot-bound
    assert kv.n_used == 4 * 2                          # 5 tokens -> 2 pages
    for slot, _ in admitted:
        sched.retire(slot)
    assert kv.n_used == 0 and len(sched.done) == 4
    assert len(sched.admit()) == 2                     # the queue drains


def test_scheduler_preempts_youngest_and_requeues_front():
    sched, kv = _sched(n_pages=7, page_size=4, max_batch=4)
    sched.submit(Request(rid=0, prompt=[1] * 8, max_new_tokens=8))
    sched.submit(Request(rid=1, prompt=[2] * 8, max_new_tokens=8))
    admitted = sched.admit()
    assert len(admitted) == 2                          # 2+2 pages of 6
    s0, r0 = admitted[0]
    s1, r1 = admitted[1]
    r0.out.append(9)
    # grow r0 past its pages: 8 -> 9 tokens needs a 3rd page; pool has 2
    # free, so no preemption yet; grow again after exhausting
    sched.grow_seq(s0)
    assert kv.n_used == 5
    kv.allocate(99, 4)                                 # eat the last free page
    r1.out.append(7)
    for _ in range(4):                                 # 9 -> 13 tokens
        sched.grow_seq(s0)
    # r1 (younger) must have been evicted to make room, r0 survives
    assert s1 not in sched.slots and s0 in sched.slots
    assert sched.waiting and sched.waiting[0] is r1
    assert r1.n_preemptions == 1
    assert r1.prefill_tokens == [2] * 8 + [7]          # recompute keeps out
    with pytest.raises(PageAllocationError):
        for _ in range(32):                            # nothing left to evict
            sched.grow_seq(s0)
    kv.free_seq(99)
    kv.free_seq(r0.rid)
    assert kv.n_used == 0


def test_scheduler_rejects_oversized_request():
    sched, _ = _sched(n_pages=4, page_size=4)
    with pytest.raises(ValueError):
        sched.submit(Request(rid=0, prompt=[1] * 10, max_new_tokens=4))


# ------------------------- engine equivalence --------------------------- #

@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("llama3.2-1b"), d_model=64, n_layers=2,
                  vocab=128)
    opts = RuntimeOptions(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), opts)
    return cfg, opts, params


def test_continuous_matches_static_equal_lengths(small_model):
    """Acceptance: token-identical greedy outputs for equal-length prompts."""
    cfg, opts, params = small_model
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 12),
                                            1, cfg.vocab))
    reqs = [p.tolist() for p in prompts]
    want = ServeEngine(cfg, params, opts, max_len=32).serve(reqs, 8)
    eng = ServeEngine(cfg, params, opts, max_len=32, scheduler="continuous",
                      page_size=8, max_batch=4)
    assert eng.serve(reqs, 8) == want


def test_continuous_matches_static_ragged(small_model):
    cfg, opts, params = small_model
    rng = np.random.default_rng(2)
    reqs = [rng.integers(1, cfg.vocab, size=n).tolist()
            for n in (5, 12, 3, 9, 7)]
    want = ServeEngine(cfg, params, opts, max_len=32).serve(reqs, 8)
    eng = ServeEngine(cfg, params, opts, max_len=32, scheduler="continuous",
                      page_size=8, max_batch=3)      # forces queueing
    assert eng.serve(reqs, 8) == want
    assert eng.stats.requests == 5
    assert eng.kv_manager.n_used == 0                # no page leak


def test_continuous_preemption_token_identical(small_model):
    cfg, opts, params = small_model
    reqs = [list(range(1, 5)), list(range(5, 9))]
    want = ServeEngine(cfg, params, opts, max_len=32).serve(reqs, 12)
    eng = ServeEngine(cfg, params, opts, max_len=32, scheduler="continuous",
                      page_size=4, max_batch=2, n_pages=6)
    assert eng.serve(reqs, 12) == want
    assert eng.stats.preemptions >= 1                # the pool forced one


def test_continuous_eos_retires_early(small_model):
    cfg, opts, params = small_model
    reqs = [[3, 4, 5], [6, 7, 8, 9]]
    ref_eng = ServeEngine(cfg, params, opts, max_len=32)
    want = ref_eng.serve(reqs, 8)
    eos = want[0][2]                                 # force an early EOS
    a = ServeEngine(cfg, params, opts, max_len=32, eos_id=eos)
    b = ServeEngine(cfg, params, opts, max_len=32, eos_id=eos,
                    scheduler="continuous", page_size=8, max_batch=2)
    outs_a, outs_b = a.serve(reqs, 8), b.serve(reqs, 8)
    assert outs_b[0][-1] == eos and len(outs_b[0]) <= 8
    # the static wave pads finished rows until the wave exits; compare the
    # continuous output against the static prefix up to and incl. EOS
    for sa, sb in zip(outs_a, outs_b):
        assert sb == sa[:len(sb)]


def test_continuous_pallas_matches_xla():
    """The served Pallas path (chunk prefill, fused paged decode, prefix
    cache with copy-on-write) emits the XLA path's greedy tokens. Widths
    are cut, but head_dim stays 64 so the kernels take the shape they are
    served at."""
    cfg = get_config("llama3.2-1b").replace(
        n_layers=2, d_model=128, n_heads=2, n_kv_heads=1, head_dim=64,
        d_ff=256, vocab=128)
    opts = RuntimeOptions(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), opts)
    rng = np.random.default_rng(4)
    doc = rng.integers(1, cfg.vocab, size=12).tolist()
    reqs = [doc + rng.integers(1, cfg.vocab, size=n).tolist()
            for n in (9, 2, 14)]
    kw = dict(max_len=40, scheduler="continuous", page_size=8, max_batch=2)
    want = ServeEngine(cfg, params, opts, **kw).serve(reqs, 6)
    eng = ServeEngine(cfg, params,
                      RuntimeOptions(dtype="float32", attn_impl="pallas"),
                      **kw)
    assert eng.serve(reqs, 6) == want
    assert eng.stats.cached_prefix_tokens > 0


def test_continuous_rejects_unsupported_config():
    cfg = reduced(get_config("mamba2-130m"), d_model=64)
    with pytest.raises(NotImplementedError):
        ServeEngine(cfg, opts=RuntimeOptions(dtype="float32"),
                    scheduler="continuous")


def test_serve_bucketed_returns_ordered_list(small_model):
    cfg, opts, params = small_model
    eng = ServeEngine(cfg, params, opts, max_len=32)
    reqs = [[1, 2, 3]] * 2 + [[5, 6, 7, 8, 9, 10]] * 3
    outs = eng.serve_bucketed(reqs, 4)
    assert isinstance(outs, list) and len(outs) == 5
    assert all(len(o) == 4 for o in outs)
    assert outs[0] == outs[1] and outs[2] == outs[3] == outs[4]


def test_generate_rejects_overlong_request(small_model):
    cfg, opts, params = small_model
    eng = ServeEngine(cfg, params, opts, max_len=16)
    with pytest.raises(AssertionError):
        eng.generate(np.ones((1, 12), np.int32), 8)   # 12 + 8 > 16
