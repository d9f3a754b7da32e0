"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is optional (requirements-dev.txt): only the property sweep
# needs it; the fixed-case kernel tests must run everywhere
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                         # pragma: no cover
    HAVE_HYPOTHESIS = False

import repro.kernels.decode_attention as da
import repro.kernels.flash_attention as fa
import repro.kernels.ref as ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _mk(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ------------------------- flash attention ---------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,dh,bq,bkv", [
    (1, 256, 4, 4, 128, 128, 128),     # MHA
    (2, 512, 8, 2, 128, 256, 256),     # GQA 4:1
    (1, 384, 4, 1, 128, 128, 128),     # MQA, non-pow2 seq
    (1, 256, 2, 2, 256, 128, 128),     # wide head
])
def test_flash_attention_matches_ref(dtype, B, S, H, Hkv, dh, bq, bkv):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _mk(ks[0], (B, S, H, dh), dtype)
    k = _mk(ks[1], (B, S, Hkv, dh), dtype)
    v = _mk(ks[2], (B, S, Hkv, dh), dtype)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True,
                             block_q=bq, block_kv=bkv)
    want = ref.flash_attention_ref(q, k, v, causal=True, scale=dh ** -0.5)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_non_causal():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _mk(ks[0], (1, 256, 4, 128), jnp.float32)
    k = _mk(ks[1], (1, 256, 4, 128), jnp.float32)
    v = _mk(ks[2], (1, 256, 4, 128), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=False, interpret=True,
                             block_q=128, block_kv=128)
    want = ref.flash_attention_ref(q, k, v, causal=False, scale=128 ** -0.5)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


if HAVE_HYPOTHESIS:
    @given(
        S=st.sampled_from([128, 256, 384, 512]),
        Hkv=st.sampled_from([1, 2, 4]),
        group=st.sampled_from([1, 2, 4]),
        dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    )
    @settings(max_examples=12, deadline=None)
    def test_flash_attention_property_sweep(S, Hkv, group, dtype):
        H = Hkv * group
        ks = jax.random.split(jax.random.PRNGKey(S * H), 3)
        q = _mk(ks[0], (1, S, H, 128), dtype)
        k = _mk(ks[1], (1, S, Hkv, 128), dtype)
        v = _mk(ks[2], (1, S, Hkv, 128), dtype)
        out = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                 block_q=128, block_kv=128)
        want = ref.flash_attention_ref(q, k, v, causal=True,
                                       scale=128 ** -0.5)
        np.testing.assert_allclose(out.astype(jnp.float32),
                                   want.astype(jnp.float32),
                                   atol=TOL[dtype], rtol=TOL[dtype])
else:
    @pytest.mark.skip(reason="hypothesis not installed "
                      "(pip install -r requirements-dev.txt)")
    def test_flash_attention_property_sweep():
        pass


# ------------------------- decode attention --------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,dh,L,bkv", [
    (2, 4, 2, 128, 1024, 256),
    (1, 8, 1, 128, 512, 128),          # MQA
    (4, 4, 4, 64, 256, 128),           # small head_dim
])
def test_decode_attention_matches_ref(dtype, B, H, Hkv, dh, L, bkv):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _mk(ks[0], (B, H, dh), dtype)
    kc = _mk(ks[1], (B, L, Hkv, dh), dtype)
    vc = _mk(ks[2], (B, L, Hkv, dh), dtype)
    valid = jax.random.randint(ks[3], (B,), 1, L + 1)
    out = da.decode_attention(q, kc, vc, valid, interpret=True, block_kv=bkv)
    want = ref.decode_attention_ref(q, kc, vc, valid, scale=dh ** -0.5)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_attention_int8_kv():
    """int8 KV halves traffic; result must track the fp16 reference within
    quantization error (the paper's traffic-reduction knob, takeaway III)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    B, H, Hkv, dh, L = 2, 8, 2, 128, 1024
    q = _mk(ks[0], (B, H, dh), jnp.float32)
    kc = _mk(ks[1], (B, L, Hkv, dh), jnp.float32)
    vc = _mk(ks[2], (B, L, Hkv, dh), jnp.float32)
    valid = jnp.array([L, L // 2], jnp.int32)
    ki, vi, ksc, vsc = da.quantize_kv(kc, vc)
    out = da.decode_attention(q, ki, vi, valid, k_scale=ksc, v_scale=vsc,
                              interpret=True, block_kv=256)
    # exact vs int8 oracle
    want_i8 = ref.decode_attention_ref(q, ki, vi, valid, scale=dh ** -0.5,
                                       k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(out, want_i8, atol=2e-5, rtol=2e-5)
    # close to the unquantized reference
    want_fp = ref.decode_attention_ref(q, kc, vc, valid, scale=dh ** -0.5)
    assert float(jnp.max(jnp.abs(out - want_fp))) < 0.05


def test_decode_attention_valid_masking():
    """Tokens beyond kv_valid must not influence the result."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, H, dh, L = 1, 4, 128, 512
    q = _mk(ks[0], (B, H, dh), jnp.float32)
    kc = _mk(ks[1], (B, L, H, dh), jnp.float32)
    vc = _mk(ks[2], (B, L, H, dh), jnp.float32)
    valid = jnp.array([300], jnp.int32)
    out1 = da.decode_attention(q, kc, vc, valid, interpret=True, block_kv=128)
    kc2 = kc.at[:, 300:].set(999.0)
    vc2 = vc.at[:, 300:].set(-999.0)
    out2 = da.decode_attention(q, kc2, vc2, valid, interpret=True,
                               block_kv=128)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


# ----------------------- kernel entry eligibility ----------------------- #

@pytest.mark.parametrize("dh,ps,dtype,softcap,rule", [
    (64, 8, jnp.bfloat16, 0.0, "page_size 8"),     # bf16 needs 16 rows
    (64, 16, jnp.int8, 0.0, "page_size 16"),       # int8 needs 32 rows
    (16, 16, jnp.float32, 0.0, "head_dim 16"),     # reduced configs
    (64, 16, jnp.float32, 30.0, "softcap"),
])
def test_pallas_entry_refuses_ineligible_shape(dh, ps, dtype, softcap, rule):
    """With attn_impl='pallas' an ineligible shape raises, naming the rule
    and the shapes — it never falls back to the XLA path in silence."""
    from repro.kernels import ops
    q = jnp.zeros((2, 4, dh), jnp.float32)
    kp = jnp.zeros((5, 2, ps, dh), dtype)
    with pytest.raises(ValueError, match=rule) as err:
        ops.paged_decode_attention(q, kp, kp, jnp.zeros((2, 2), jnp.int32),
                                   jnp.ones((2,), jnp.int32), scale=1.0,
                                   softcap=softcap)
    assert str(tuple(kp.shape)) in str(err.value)
