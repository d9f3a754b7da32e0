"""The chunk programs' whole-page pool writes (DESIGN.md SS11, SS14).

``prefill_paged_chunk`` and ``decode_verify_paged`` carry the KV pool
through the layer scan and write each layer's pages whole, at (layer,
page). The oracle below is the earlier form, kept here to hold the new
one to it: each layer's slice of the pool rides the scan as xs/ys and
the chunk's K/V land one token row at a time. Logits and every valid
pool position must match bitwise, and no page outside the chunk's
window may change."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.models import RuntimeOptions, init_params
from repro.models import common as cm
from repro.models import lm

N_PAGES = 24


def _oracle(cfg, params, tokens, cache, page_table, start, n_valid, opts,
            calibrate=False):
    """Per-row form: pool slices as scan xs/ys, token-row scatter."""
    B, C = tokens.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    start = jnp.asarray(start, jnp.int32)
    positions = jnp.broadcast_to(start[..., None] + jnp.arange(C), (B, C))
    n_pp = page_table.shape[1]

    def body(h, xs):
        lp, cl = xs
        p, xn = lp["attn"], cm.rms_norm(h, lp["ln1"])
        q = cm.apply_rope(cm.dense(p["wq"], xn).reshape(B, C, H, hd),
                          positions)
        k = cm.apply_rope(cm.dense(p["wk"], xn).reshape(B, C, Hkv, hd),
                          positions)
        v = cm.dense(p["wv"], xn).reshape(B, C, Hkv, hd)
        kp, vp = cl["k"], cl["v"]
        ps = kp.shape[2]
        new = {}
        if "k_scale" in cl:
            if calibrate:
                ok = (positions < n_valid[:, None])[..., None, None]
                ksc = lm._amax_scale(jnp.where(ok, k, 0), (0, 1, 3))
                vsc = lm._amax_scale(jnp.where(ok, v, 0), (0, 1, 3))
            else:
                ksc, vsc = cl["k_scale"], cl["v_scale"]
            k = lm._quantize_with(k, ksc[None, None]).astype(jnp.int8)
            v = lm._quantize_with(v, vsc[None, None]).astype(jnp.int8)
            new = {"k_scale": ksc, "v_scale": vsc}
        else:
            ksc = vsc = None
        blk = positions // ps
        pid = jnp.take_along_axis(page_table, jnp.minimum(blk, n_pp - 1), 1)
        pid = jnp.where(blk < n_pp, pid, 0).reshape(-1)
        off = (positions % ps).reshape(-1)
        kp = kp.at[pid, :, off].set(k.reshape(B * C, Hkv, hd).astype(kp.dtype))
        vp = vp.at[pid, :, off].set(v.reshape(B * C, Hkv, hd).astype(vp.dtype))
        out = lm._chunk_attend(q, kp, vp, ksc, vsc, page_table, start,
                               n_valid, cfg=cfg, opts=opts)
        h = h + cm.dense(p["wo"], out.reshape(B, C, H * hd))
        f, _ = lm._ffn_apply(lp, cm.rms_norm(h, lp["ln2"]), cfg, opts)
        return h + f, {"k": kp, "v": vp, **new}
    x = lm._embed_tokens(cfg, params, tokens, None)
    x, st = jax.lax.scan(body, x, (params["stack"], cache["stack"]))
    return lm._logits(cfg, params, x), {"stack": st}


@pytest.fixture(scope="module")
def chunk_model():
    cfg = reduced(get_config("llama3.2-1b"), d_model=64, n_layers=2,
                  vocab=128)
    params = init_params(cfg, jax.random.PRNGKey(0),
                         RuntimeOptions(dtype="float32"))
    return cfg, params


def _filled_pool(cfg, ps, opts, key):
    """A pool whose every slot holds a distinct value, so that a stray
    write shows."""
    cache = lm.init_paged_cache(cfg, N_PAGES, ps, opts)
    st = dict(cache["stack"])
    kk, kv = jax.random.split(key)
    for name, k in (("k", kk), ("v", kv)):
        val = jax.random.normal(k, st[name].shape, jnp.float32)
        if st[name].dtype == jnp.int8:
            val = jnp.clip(jnp.round(val * 40), -127, 127)
        st[name] = val.astype(st[name].dtype)
    if "k_scale" in st:
        st["k_scale"] = jnp.full_like(st["k_scale"], 0.03)
        st["v_scale"] = jnp.full_like(st["v_scale"], 0.05)
    return {"stack": st}


# (id, page tables, start (int, or per-row for the verify pass), n_valid,
#  chunk length C, page size)
CASES = [
    ("aligned", [[3, 5, 7, 9, 11, 13]], 8, [16], 8, 4),
    ("partial_page_prefix", [[3, 5, 7, 9, 11, 13]], 6, [14], 8, 4),
    ("padded_final_chunk", [[3, 5, 7, 9, 11, 13]], 10, [13], 8, 4),
    ("past_the_table", [[3, 5, 7]], 8, [12], 8, 4),
    ("ragged_batch", [[3, 5, 7, 9, 11, 13], [2, 4, 6, 8, 10, 12]], 8,
     [16, 11], 8, 4),
    # both rows read shared prefix pages 1 and 2; page 3, which row 0
    # writes, also lies in row 1's window, untouched by row 1
    ("shared_page", [[1, 2, 3, 4, 5, 6], [1, 2, 7, 8, 3, 9]], 8, [16, 12],
     8, 4),
    # per-row starts; row 1's six tokens span three pages of four
    ("verify", [[3, 5, 7, 9, 11, 13], [2, 4, 6, 8, 10, 12]], [5, 11],
     [9, 17], 6, 4),
]
# the verify pass never calibrates
PARAMS = [pytest.param(case, kv, id=f"{case[0]}-{kv}")
          for case in CASES for kv in ("native", "int8", "int8_calibrate")
          if not (isinstance(case[2], list) and kv == "int8_calibrate")]


def _window_ids(tables, start, C, ps):
    """Every page id in some row's window of the chunk."""
    ids = set()
    for b, t in enumerate(tables):
        s = start[b] if isinstance(start, list) else start
        for blk in range(s // ps, (s + C - 1) // ps + 1):
            if blk < len(t):
                ids.add(t[blk])
    return ids


@pytest.mark.parametrize("case,kv", PARAMS)
def test_chunk_program_matches_row_scatter(chunk_model, case, kv):
    cfg, params = chunk_model
    _, tables, start, n_valid, C, ps = case
    opts = RuntimeOptions(dtype="float32",
                          cache_dtype=None if kv == "native" else "int8")
    calibrate = kv == "int8_calibrate"
    cache = _filled_pool(cfg, ps, opts, jax.random.PRNGKey(1))
    B = len(tables)
    pt = jnp.asarray(tables, jnp.int32)
    nv = jnp.asarray(n_valid, jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, C), 1, cfg.vocab)
    verify = isinstance(start, list)
    st = jnp.asarray(start, jnp.int32)

    want_lg, want = jax.jit(
        lambda c: _oracle(cfg, params, tokens, c, pt, st, nv, opts,
                          calibrate=calibrate))(cache)
    if verify:
        got_lg, got = jax.jit(lambda c: lm.decode_verify_paged(
            cfg, params, tokens, st, nv - st, pt, c, opts))(cache)
    else:
        got_lg, got = jax.jit(lambda c: lm.prefill_paged_chunk(
            cfg, params, tokens, c, pt, st, nv, opts,
            calibrate=calibrate))(cache)

    np.testing.assert_array_equal(np.asarray(got_lg), np.asarray(want_lg))
    for name in ("k_scale", "v_scale"):
        if name in want["stack"]:
            np.testing.assert_array_equal(np.asarray(got["stack"][name]),
                                          np.asarray(want["stack"][name]))
    window = _window_ids(tables, start, C, ps)
    for name in ("k", "v"):
        before = np.asarray(cache["stack"][name])
        new = np.asarray(got["stack"][name])
        ref = np.asarray(want["stack"][name])
        # every valid position of every row, prefix pages included
        for b, t in enumerate(tables):
            for pos in range(n_valid[b]):
                pid, off = t[pos // ps], pos % ps
                np.testing.assert_array_equal(new[:, pid, :, off],
                                              ref[:, pid, :, off])
        # pages outside every row's window, other sequences' included
        for pid in range(1, N_PAGES):
            if pid not in window:
                np.testing.assert_array_equal(new[:, pid], before[:, pid])
