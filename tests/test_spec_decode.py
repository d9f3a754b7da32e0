"""Speculative decoding on the fused paged path (DESIGN.md SS14).

Covers the multi-query verify kernel vs its jnp oracle (f32 + int8,
page-boundary causal masking), leftover/rejection sampling correctness
(greedy identity + distribution sanity), the manager's
``commit_speculative`` partial-rollback protocol (unit + hypothesis
trace), the draft proposers, and engine-level token identity: spec-on at
temperature 0 equals spec-off for both draft modes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.decode_attention as da
import repro.kernels.ref as ref
from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.models import RuntimeOptions, init_params, sampling
from repro.serving import (AdaptiveSpecK, ModelDraft, NGramDraft,
                           PagedKVManager, Request, ServeEngine)


# --------------------------- verify kernel ------------------------------ #

@pytest.mark.parametrize("B,H,Hkv,dh,ps,C,lens,fed", [
    (2, 8, 2, 64, 16, 8, (40, 17), (8, 5)),   # GQA, ragged starts
    (1, 4, 1, 128, 16, 4, (30,), (3,)),       # MQA, window crosses a page
    (2, 4, 4, 64, 8, 8, (8, 15), (1, 8)),     # MHA, fed=1 == plain decode
])
def test_spec_verify_kernel_matches_oracle(B, H, Hkv, dh, ps, C, lens, fed):
    """Acceptance: the Pallas verify pass matches the jnp oracle in
    interpret mode, per-row causal masking included — row j of slot b
    attends exactly ``lens[b] + min(j, fed[b] - 1) + 1`` positions."""
    L = max(l + C for l in lens)
    npp = -(-L // ps) + 1
    P = B * npp + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, C, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, ps, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, ps, dh), jnp.float32)
    perm = np.asarray(jax.random.permutation(ks[0], P - 1)) + 1
    pt = jnp.asarray(perm[:B * npp].reshape(B, npp), jnp.int32)
    sl = jnp.asarray(lens, jnp.int32)
    nf = jnp.asarray(fed, jnp.int32)
    out = da.spec_verify_attention(q, kp, vp, pt, sl, nf, interpret=True)
    want = ref.spec_verify_attention_ref(q, kp, vp, pt, sl, nf,
                                         scale=dh ** -0.5)
    for b in range(B):
        np.testing.assert_allclose(out[b, :fed[b]], want[b, :fed[b]],
                                   atol=1e-5, rtol=1e-5)


def test_spec_verify_kernel_int8():
    B, C, H, Hkv, dh, ps, npp = 1, 8, 8, 2, 64, 32, 3
    P = npp + 2
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, C, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, ps, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, ps, dh), jnp.float32)
    pt = jnp.asarray([[2, 3, 1]], jnp.int32)
    sl, nf = jnp.asarray([40], jnp.int32), jnp.asarray([8], jnp.int32)
    ki, vi, ksc, vsc = da.quantize_kv(kp, vp, head_axis=1)
    out = da.spec_verify_attention(q, ki, vi, pt, sl, nf, k_scale=ksc,
                                   v_scale=vsc, interpret=True)
    want = ref.spec_verify_attention_ref(q, ki, vi, pt, sl, nf,
                                         scale=dh ** -0.5,
                                         k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    fp = ref.spec_verify_attention_ref(q, kp, vp, pt, sl, nf,
                                       scale=dh ** -0.5)
    assert float(jnp.max(jnp.abs(out - fp))) < 0.05


def test_spec_verify_rows_ignore_later_draft_kv():
    """Causal independence across the verify window: corrupting the KV of
    fed position j must leave rows 0..j-1 untouched (page-boundary case:
    the window spans two pages)."""
    B, C, H, Hkv, dh, ps = 1, 4, 4, 2, 64, 4
    lens, fed = 6, 4                       # window occupies slots 6..9:
    npp = 4                                # crosses the page-1 boundary
    P = npp + 1
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, C, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, ps, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, ps, dh), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    sl = jnp.asarray([lens], jnp.int32)
    nf = jnp.asarray([fed], jnp.int32)
    base = da.spec_verify_attention(q, kp, vp, pt, sl, nf, interpret=True)
    # corrupt the LAST fed position's KV (token index lens+fed-1 = 9,
    # page 2 slot 1) — only the final row may see it
    kp2 = kp.at[3, :, 1].set(100.0)
    vp2 = vp.at[3, :, 1].set(-100.0)
    out = da.spec_verify_attention(q, kp2, vp2, pt, sl, nf, interpret=True)
    np.testing.assert_allclose(out[:, :fed - 1], base[:, :fed - 1],
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(out[:, fed - 1] - base[:, fed - 1]))) > 1.0


# ------------------------ accept / reject rules ------------------------- #

def test_spec_accept_greedy_prefix_and_correction():
    """Greedy accept = longest argmax-matching prefix; the emitted block is
    [accepted drafts, correction from the first rejected row, pads]."""
    V = 8
    tgt_rows = np.asarray([[1, 2, 3, 4], [5, 0, 0, 0]])       # argmax chain
    logits = np.full((2, 4, V), -5.0, np.float32)
    for b in range(2):
        for j in range(4):
            logits[b, j, tgt_rows[b, j]] = 5.0
    draft = jnp.asarray([[1, 2, 9], [6, 0, 0]], jnp.int32)    # b0: 2 match
    dl = jnp.asarray([3, 3], jnp.int32)                       # b1: 0 match
    keys = jnp.zeros((2, 2), jnp.uint32)
    out, n_acc, _ = sampling.spec_accept(jnp.asarray(logits), draft, dl,
                                         keys, temperature=0.0, pad_id=0)
    np.testing.assert_array_equal(np.asarray(n_acc), [2, 0])
    np.testing.assert_array_equal(np.asarray(out),
                                  [[1, 2, 3, 0], [5, 0, 0, 0]])


def test_spec_accept_full_acceptance_emits_bonus():
    V = 8
    logits = np.full((1, 3, V), -5.0, np.float32)
    for j, t in enumerate([4, 5, 6]):
        logits[0, j, t] = 5.0
    out, n_acc, _ = sampling.spec_accept(
        jnp.asarray(logits), jnp.asarray([[4, 5]], jnp.int32),
        jnp.asarray([2], jnp.int32), jnp.zeros((1, 2), jnp.uint32),
        temperature=0.0)
    assert int(n_acc[0]) == 2
    np.testing.assert_array_equal(np.asarray(out), [[4, 5, 6]])


def test_rejection_sampling_matches_target_distribution():
    """Distribution sanity (chi-square-loose / total-variation): for a
    one-hot draft the accept-or-leftover construction is exactly unbiased
    — P(emit x) = p(x) for EVERY fixed draft d — so the empirical first
    token over many keys must track softmax(logits/T)."""
    V, N = 6, 6000
    row = np.asarray([1.2, 0.3, -0.4, 2.0, 0.0, -1.0], np.float32)
    logits = jnp.asarray(np.tile(row, (N, 2, 1)))      # C=2: 1 draft+bonus
    want = np.asarray(jax.nn.softmax(jnp.asarray(row) / 0.9))
    for d in (3, 1):                                   # likely + unlikely
        draft = jnp.full((N, 1), d, jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(d), N)
        out, n_acc, _ = sampling.spec_accept(
            logits, draft, jnp.ones((N,), jnp.int32), keys, temperature=0.9)
        first = np.asarray(out[:, 0])
        emp = np.bincount(first, minlength=V) / N
        assert 0.5 * np.abs(emp - want).sum() < 0.05
        # acceptance rate itself must track p(d)
        assert abs(np.asarray(n_acc).mean() - want[d]) < 0.05


# -------------------- manager: partial rollback ------------------------- #

def test_commit_speculative_partial_rollback_unit():
    kv = PagedKVManager(n_pages=9, page_size=4)
    kv.allocate(0, 6)                                  # 2 pages, slot 6 next
    used0 = kv.n_used
    claimed = kv.reserve_ahead(0, 5)                   # covers tokens 6..10
    assert len(claimed) == 1                           # page for 8..11
    rolled = kv.commit_speculative(0, 1)               # accept 1 of 5
    assert kv.seq_len(0) == 7
    assert rolled == 1                                 # surplus page freed
    assert kv.n_used == used0
    # re-reserve after rollback: the protocol is reentrant
    kv.reserve_ahead(0, 5)                             # 7 + 5 -> 12: 1 new
    rolled = kv.commit_speculative(0, 5)               # full acceptance
    assert kv.seq_len(0) == 12 and rolled == 0
    assert kv.n_used == used0 + 1


def test_commit_speculative_hypothesis_trace():
    """Random reserve/verify/rollback traces preserve the invariants:
    pages exactly cover the landed extent after every commit_speculative,
    the landed length equals the sum of accepted counts, and no page
    leaks (total used == pages_needed of every live sequence)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 2),      # seq id
                              st.integers(1, 6),      # draft_len + 1
                              st.floats(0.0, 1.0)),   # acceptance fraction
                    min_size=1, max_size=25))
    def trace(ops):
        ps = 4
        kv = PagedKVManager(n_pages=64, page_size=ps)
        landed = {}
        for sid, window, frac in ops:
            if sid not in landed:
                kv.allocate(sid, 2)
                landed[sid] = 2
            kv.reserve_ahead(sid, window)
            acc = int(round(frac * window))
            kv.commit_speculative(sid, acc)
            landed[sid] += acc
            assert kv.seq_len(sid) == landed[sid]
            pages = kv._seqs[sid].pages
            assert len(pages) == kv.pages_needed(landed[sid])
        total = sum(kv.pages_needed(n) for n in landed.values())
        assert kv.n_used == total

    trace()


# ----------------------------- drafters --------------------------------- #

def test_ngram_draft_unrolls_loops_to_full_k():
    """A period-2 decode loop must draft the full window, not truncate at
    the latest occurrence (the iterated-rollout property)."""
    d = NGramDraft(max_ngram=3, min_ngram=1)
    req = Request(rid=0, prompt=[9, 1, 2, 1, 2, 1, 2], max_new_tokens=8)
    got = d.propose(req, 6)
    assert got == [1, 2, 1, 2, 1, 2]
    assert d.propose(Request(rid=1, prompt=[3, 4, 5], max_new_tokens=8),
                     4) == []                          # no repeat: no draft
    d.drop(0)
    assert 0 not in d._idx and 0 not in d._seen


def test_ngram_draft_prefers_longest_match():
    d = NGramDraft(max_ngram=3, min_ngram=1)
    # trailing [7,8] occurs earlier followed by 5; trailing [8] also occurs
    # followed by 6 — the longer match must win
    req = Request(rid=0, prompt=[7, 8, 5, 0, 8, 6, 0, 7, 8],
                  max_new_tokens=4)
    assert d.propose(req, 1) == [5]


def test_adaptive_spec_k_tracks_acceptance():
    a = AdaptiveSpecK(8, k_min=1, beta=0.5)
    r = Request(rid=0, prompt=[1], max_new_tokens=4)
    assert a.k_for(r) == 8                             # optimistic start
    for _ in range(6):
        a.update(r, 8, 0)                              # everything rejected
    assert a.k_for(r) == 1
    for _ in range(6):
        a.update(r, 8, 8)
    assert a.k_for(r) == 8
    a.update(r, 0, 0)                                  # no-op: nothing asked
    assert a.k_for(r) == 8
    with pytest.raises(ValueError):
        AdaptiveSpecK(0)


def test_model_draft_sync_catchup_propose():
    """Protocol unit: admit syncs to the target's landed extent, catch-up
    absorbs committed tokens, propose returns k tokens and rolls its
    reservation back (landed draft extent unchanged)."""
    cfg = reduced(get_config("llama3.2-1b"), d_model=32, n_layers=1,
                  vocab=64)
    d = ModelDraft(cfg, page_size=4, max_batch=2, max_len=32)
    req = Request(rid=7, prompt=[3, 1, 4, 1, 5], max_new_tokens=8)
    out = d.propose_all([(req, 3)])
    assert set(out) == {7} and len(out[7]) == 3
    assert all(0 <= t < cfg.vocab for t in out[7])
    assert d.kv.seq_len(7) == len(req.prefill_tokens) - 1   # rolled back
    req.out.extend([9, 2])                     # target committed 2 tokens
    out2 = d.propose_all([(req, 3)])
    assert d.kv.seq_len(7) == len(req.prefill_tokens) - 1   # caught up
    assert len(out2[7]) == 3
    # determinism given the same request state (one-hot draft assumption)
    assert d.propose_all([(req, 3)])[7] == out2[7]
    d.drop(7)
    assert d.kv.n_used == 0


# --------------------------- engine identity ---------------------------- #

@pytest.fixture(scope="module")
def spec_model():
    cfg = reduced(get_config("llama3.2-1b"), d_model=64, n_layers=2,
                  vocab=128)
    opts = RuntimeOptions(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), opts)
    rng = np.random.default_rng(0)
    doc = rng.integers(1, 120, size=40).tolist()
    reqs = [doc + rng.integers(1, 120, size=5).tolist() for _ in range(3)]
    return cfg, opts, params, reqs


def _serve(cfg, params, opts, reqs, *, prefix=True, **kw):
    eng = ServeEngine(cfg, params, opts, max_len=96, max_batch=2,
                      scheduler="continuous", page_size=8, prefill_chunk=16,
                      prefix_cache=prefix, **kw)
    return eng.serve([r[:] for r in reqs], max_new_tokens=10), eng.stats


def test_engine_ngram_spec_token_identity(spec_model):
    """Acceptance (fast lane): spec-on at temperature 0 is token-identical
    to spec-off, and drafts actually land."""
    cfg, opts, params, reqs = spec_model
    want, _ = _serve(cfg, params, opts, reqs)
    got, s = _serve(cfg, params, opts, reqs, spec_mode="ngram", spec_k=4)
    assert got == want
    assert s.spec_blocks > 0 and s.draft_accepted > 0
    assert 0.0 < s.acceptance_rate <= 1.0


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("prefix", [True, False])
def test_engine_ngram_spec_identity_matrix(spec_model, k, prefix):
    cfg, opts, params, reqs = spec_model
    want, _ = _serve(cfg, params, opts, reqs, prefix=prefix)
    got, _ = _serve(cfg, params, opts, reqs, prefix=prefix,
                    spec_mode="ngram", spec_k=k)
    assert got == want


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 4])
def test_engine_model_draft_identity(spec_model, k):
    cfg, opts, params, reqs = spec_model
    dcfg = reduced(get_config("llama3.2-1b"), d_model=32, n_layers=1,
                   vocab=128)
    want, _ = _serve(cfg, params, opts, reqs)
    got, s = _serve(cfg, params, opts, reqs, spec_mode="model", spec_k=k,
                    draft_cfg=dcfg)
    assert got == want
    assert s.spec_blocks > 0


def test_engine_spec_flag_validation(spec_model):
    cfg, opts, params, _ = spec_model
    mk = lambda **kw: ServeEngine(cfg, params, opts, max_len=64,
                                  scheduler="continuous", **kw)
    with pytest.raises(ValueError, match="spec_mode"):
        mk(spec_mode="banana")
    with pytest.raises(ValueError, match="continuous"):
        ServeEngine(cfg, params, opts, max_len=64, scheduler="static",
                    spec_mode="ngram")
    with pytest.raises(ValueError, match="draft_cfg"):
        mk(spec_mode="model")                  # model mode needs a config
    with pytest.raises(ValueError, match="draft_cfg"):
        mk(draft_cfg=cfg)                      # config needs model mode
    with pytest.raises(ValueError, match="temperature"):
        mk(top_k=5)                            # filters need temperature
    with pytest.raises(ValueError, match="spec_k"):
        mk(spec_mode="ngram", spec_k=0)


def test_engine_stall_attribution_per_request(spec_model):
    """Satellite: ServeStats.stall_by_rid partitions the recorded stall."""
    from repro.core import hbs, lpddr6, npu_hierarchy
    cfg, opts, params, reqs = spec_model
    hier = npu_hierarchy(lpddr6(capacity_gb=2e-5),
                         hbs(0.001, latency_us=50.0, capacity_gb=1.0))
    eng = ServeEngine(cfg, params, opts, max_len=96, max_batch=2,
                      scheduler="continuous", page_size=8, prefill_chunk=16,
                      hierarchy=hier, hbs_gbps=0.001, hbs_latency_us=50.0)
    eng.serve([r[:] for r in reqs], max_new_tokens=10)
    s = eng.stats
    assert s.stall_s > 0
    assert s.stall_by_rid
    assert all(v > 0 for v in s.stall_by_rid.values())
    # each barrier absorbs the batch MAX while charging every request its
    # own pages' wait, so no single request can out-accrue the total
    assert max(s.stall_by_rid.values()) <= s.stall_s + 1e-9
