"""Substrate tests: data determinism, checkpoint atomicity/resume,
fault-tolerant train loop, serving engine + tiered KV policy."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.data.pipeline import SyntheticTextDataset
from repro.models import RuntimeOptions, init_params
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.serving import ServeEngine
from repro.train import TrainConfig, train

OPTS = RuntimeOptions(dtype="float32")


# ------------------------------ data ----------------------------------- #

def test_data_pure_function_of_step():
    ds = SyntheticTextDataset(vocab=64, seq_len=16, global_batch=4, seed=3)
    a = ds.batch_at(7)
    b = ds.batch_at(7)
    c = ds.batch_at(8)
    assert jnp.array_equal(a["tokens"], b["tokens"])
    assert not jnp.array_equal(a["tokens"], c["tokens"])
    assert int(a["tokens"].max()) < 64 and int(a["tokens"].min()) >= 0


# ---------------------------- optimizer -------------------------------- #

def test_adamw_descends_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                      weight_decay=0.0, clip_norm=1e9)
    params = {"w": jnp.array([3.0, -2.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}     # d/dw of w^2
        params, state, _ = adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    assert float(cosine_schedule(cfg, 0)) == 0.0
    assert float(cosine_schedule(cfg, 10)) == pytest.approx(1e-3)
    assert float(cosine_schedule(cfg, 100)) == pytest.approx(1e-4, rel=0.01)


# ---------------------------- checkpoint ------------------------------- #

def test_checkpoint_roundtrip_and_gc(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nest": {"b": jnp.ones((4,), jnp.bfloat16)},
            "lst": [jnp.zeros((2,)), jnp.full((2,), 7.0)]}
    for s in (5, 10, 15, 20):
        save_checkpoint(tmp_path, s, tree, keep=2)
    assert latest_step(tmp_path) == 20
    # GC kept only the last 2
    steps = sorted(int(p.name.split("_")[1])
                   for p in pathlib.Path(tmp_path).glob("step_*"))
    assert steps == [15, 20]
    got, step = restore_checkpoint(tmp_path, tree)
    assert step == 20
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_checkpoint_partial_write_is_invisible(tmp_path):
    tree = {"a": jnp.ones((2,))}
    save_checkpoint(tmp_path, 1, tree)
    # simulate a crashed writer: stale tmp dir must be ignored
    (pathlib.Path(tmp_path) / "step_9.tmp").mkdir()
    assert latest_step(tmp_path) == 1


# ---------------------------- train loop ------------------------------- #

def _tiny_cfg():
    return reduced(get_config("yi-6b"), d_model=32, n_layers=2, vocab=64)


def test_train_loss_decreases_and_resumes(tmp_path):
    cfg = _tiny_cfg()
    tcfg = TrainConfig(steps=12, seq_len=32, global_batch=4, ckpt_every=6,
                       ckpt_dir=str(tmp_path), log_every=100,
                       optimizer=AdamWConfig(lr=3e-3, warmup_steps=2,
                                             total_steps=12))
    out = train(cfg, tcfg, OPTS, log_fn=None)
    assert out["last_step"] == 12
    assert out["losses"][-1] < out["losses"][0]
    # resume: continue to 16 steps from the step-12 checkpoint
    tcfg2 = TrainConfig(**{**tcfg.__dict__, "steps": 16})
    out2 = train(cfg, tcfg2, OPTS, log_fn=None)
    assert out2["last_step"] == 16
    assert len(out2["losses"]) == 4      # only steps 12..15 re-run
    # metrics log exists and is parseable
    lines = (tmp_path / "metrics.jsonl").read_text().strip().splitlines()
    assert len(lines) >= 16
    json.loads(lines[-1])


def test_train_grad_accum_matches_single_batch():
    """n_micro=2 must equal n_micro=1 up to float tolerance."""
    cfg = _tiny_cfg()

    def run(n_micro):
        tcfg = TrainConfig(steps=3, seq_len=16, global_batch=4,
                           n_micro=n_micro, ckpt_every=1000,
                           ckpt_dir=f"/tmp/repro_na_{n_micro}",
                           optimizer=AdamWConfig(lr=1e-3, warmup_steps=0,
                                                 total_steps=3))
        return train(cfg, tcfg, OPTS, log_fn=None)["losses"]
    l1, l2 = run(1), run(2)
    np.testing.assert_allclose(l1, l2, rtol=2e-3)


# ------------------------------ serving -------------------------------- #

def test_serve_engine_greedy_deterministic():
    cfg = _tiny_cfg()
    eng = ServeEngine(cfg, opts=OPTS, max_len=128, seed=0)
    prompts = jnp.ones((2, 8), jnp.int32)
    out1 = eng.generate(prompts, 6)
    out2 = eng.generate(prompts, 6)
    assert out1 == out2
    assert len(out1) == 2 and len(out1[0]) == 6
    assert eng.stats.tps > 0


def test_serve_bucketed_ragged_requests():
    cfg = _tiny_cfg()
    eng = ServeEngine(cfg, opts=OPTS, max_len=128)
    reqs = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 8, 7]]
    outs = eng.serve_bucketed(reqs, 4)
    assert len(outs) == 3 and all(len(o) == 4 for o in outs)


def test_tiered_kv_int8_close_to_native():
    """The int8 tiered-KV policy must track native-cache outputs."""
    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0), OPTS)
    e_native = ServeEngine(cfg, params, OPTS, kv_policy="native", max_len=128)
    e_int8 = ServeEngine(cfg, params, OPTS, kv_policy="int8", max_len=128)
    prompts = jnp.asarray(
        np.random.RandomState(0).randint(1, cfg.vocab, (2, 16)), jnp.int32)
    o_native = e_native.generate(prompts, 8)
    o_int8 = e_int8.generate(prompts, 8)
    agree = np.mean([a == b for ra, rb in zip(o_native, o_int8)
                     for a, b in zip(ra, rb)])
    assert agree >= 0.75, f"int8 KV diverged: agreement {agree}"


# --------------------------- compile cache ----------------------------- #

def test_compile_cache_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cc.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
        assert cc.use_compile_cache() == str(want)
        assert jax.config.jax_compilation_cache_dir == str(want)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
