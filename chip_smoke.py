#!/usr/bin/env python3
"""Serve full-width llama3.2-1b on a TPU once, through the normal engine.

    python chip_smoke.py              # one chip: XLA and Pallas attention
    python chip_smoke.py --chips 4    # four chips: shards=4 vs shards=1

One process builds ``ServeEngine(scheduler="continuous")`` the way
``repro.launch.serve`` does and serves eight requests through it:
continuous scheduler -> paged, tiered KV manager -> attention over the
page pool. The model is ``configs/llama32_1b.py`` at its published widths
(16 layers, d_model 2048, 32 heads of 64, vocab 128256) with bf16 weights
drawn from ``--seed``; there are no weight files. Prompts are ragged
(128-1024 tokens) around a shared 256-token prefix, so the prefix cache
and copy-on-write run, and the fast KV tier is capped so that some pages
live in the (simulated) offload tier.

One chip: the serve runs once with ``attn_impl="xla"`` and once with
``"pallas"``. It fails unless every request completes with all its
tokens, the Pallas run's prefill-chunk and decode-block programs contain
the Pallas kernels (``tpu_custom_call``), the last-prompt-position
logits of the two paths agree within ``LOGIT_RTOL``, and each serve's
trace reconciles with its counters.

``--chips 4``: only the head-sharded serve (``shards=4``, Pallas) and the
``shards=1`` serve it is compared with, on the first device, both with
the same weights. Greedy tokens must be identical, and the KV pool must hold a quarter of the KV
heads on each of the four devices.

The last line of standard output is the JSON result, printed only when
every check passed. Without a TPU the script exits non-zero before it
builds anything. Compiled programs are cached as
``repro.launch.compile_cache`` describes.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

PREFIX = 256                     # shared leading tokens (prefix cache + COW)
PROMPT_LENS = (1024, 200, 640, 128, 896, 333, 512, 777)
NEW_TOKENS = 32
MAX_LEN = max(PROMPT_LENS) + NEW_TOKENS
PAGE_SIZE = 16
MAX_BATCH = 8
KV_FAST_MB = 128.0               # fast KV tier cap; the rest offloads
# bf16 weights and activations: the two attention paths differ in where
# they round (the kernels accumulate in f32, the XLA path feeds bf16
# probabilities to the PV product), and 16 layers compound it. Agreement
# is max |logit_xla - logit_pallas| <= LOGIT_RTOL * max |logit_xla|.
LOGIT_RTOL = 0.05


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileCounter:
    """Backend compiles and persistent-cache hits seen by this process."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, *args, **kwargs):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, *args, **kwargs):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def line(self) -> str:
        return (f"backend_compiles={self.compiles} "
                f"compile_s={self.compile_s:.3f} "
                f"cache_hits={self.cache_hits}")


def model_config():
    from repro.configs import get_config
    cfg = get_config("llama3.2-1b")
    widths = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim,
              cfg.vocab)
    check(widths == (16, 2048, 32, 64, 128256),
          f"llama3.2-1b is not at its published widths: {widths}")
    return cfg


def make_requests(vocab: int, seed: int):
    """Ragged prompts; each starts with (a prefix of) one shared document,
    so short prompts end mid-page inside cached pages (copy-on-write)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    doc = rng.integers(1, vocab, PREFIX).tolist()
    reqs = []
    for n in PROMPT_LENS:
        head = doc[:min(n, PREFIX)]
        reqs.append(head + rng.integers(1, vocab, n - len(head)).tolist())
    return reqs


def build_engine(cfg, attn_impl: str, *, seed: int, shards: int = 1,
                 params=None):
    """The engine as ``repro.launch.serve`` builds it for
    ``--scheduler continuous --dtype bfloat16 --kv-fast-mb`` (the fast
    tier is per device: a head shard holds 1/shards of each page, so the
    cap shrinks with it and the fast tier holds as many pages as on one
    device)."""
    from repro.core import hbs, lpddr6, npu_hierarchy
    from repro.models import RuntimeOptions
    from repro.serving import ServeEngine
    hier = npu_hierarchy(lpddr6(capacity_gb=KV_FAST_MB / shards / 1e3),
                         hbs(8.0, latency_us=20.0, capacity_gb=64.0))
    return ServeEngine(cfg, params=params,
                       opts=RuntimeOptions(dtype="bfloat16",
                                           attn_impl=attn_impl),
                       kv_policy="native", max_len=MAX_LEN, seed=seed,
                       scheduler="continuous", page_size=PAGE_SIZE,
                       max_batch=MAX_BATCH, hierarchy=hier, shards=shards)


def serve(eng, reqs, label: str, counter: CompileCounter):
    """One serve through the engine; checks completion, offload use and
    the trace audit, and prints what it cost."""
    t0 = time.perf_counter()
    outs = eng.serve(reqs, NEW_TOKENS)
    wall = time.perf_counter() - t0
    s = eng.stats
    check(len(outs) == len(reqs), f"{label}: {len(outs)} of {len(reqs)} "
          f"requests came back")
    short = [i for i, o in enumerate(outs) if len(o) != NEW_TOKENS]
    check(not short, f"{label}: requests {short} did not get "
          f"{NEW_TOKENS} tokens")
    check(bool(eng.trace_report) and eng.trace_report.get("ok") is True,
          f"{label}: trace reconcile failed: {eng.trace_report}")
    check(s.cached_prefix_tokens > 0,
          f"{label}: the prefix cache served no tokens")
    offload = eng.tier_budget.offload_tier
    split = dict(s.kv_split_at_peak)
    check(split.get(offload, 0.0) > 0.0,
          f"{label}: no page took the {offload} tier (split {split})")
    say(f"{label}: wall_s={wall:.3f} requests={len(outs)} "
        f"new_tokens={s.new_tokens} prefill_tokens_computed="
        f"{s.prefill_tokens_computed} cached_prefix_tokens="
        f"{s.cached_prefix_tokens} cow_copies={s.cow_copies} "
        f"preemptions={s.preemptions} host_syncs={s.host_syncs}")
    say(f"{label}: kv_split_at_peak={s.kv_split_at_peak} "
        f"pages_spilled={s.pages_spilled} pages_fetched={s.pages_fetched} "
        f"prefill_programs={s.prefill_compiles} "
        f"decode_programs={s.decode_compiles} {counter.line()}")
    say(f"{label}: trace reconcile ok={eng.trace_report['ok']}")
    return outs


def last_position_logits(eng, prompt):
    """Logits at the prompt's last position, computed chunk by chunk
    through the engine's own compiled prefill-chunk program on a fresh
    page pool (pages 1.. in order)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import init_paged_cache
    ps, C, n_pp = eng.page_size, eng.prefill_chunk, eng.n_pages_per_seq
    cache = init_paged_cache(eng.cfg, eng.kv_manager.n_pages, ps, eng.opts)
    table = np.zeros((1, n_pp), np.int32)
    n_pages = -(-len(prompt) // ps)
    table[0, :n_pages] = np.arange(1, n_pages + 1)
    for start in range(0, len(prompt), C):
        n_real = min(C, len(prompt) - start)
        toks = np.zeros((1, C), np.int32)
        toks[0, :n_real] = prompt[start:start + n_real]
        logits, cache = eng._prefill_chunk(
            eng.params, jnp.asarray(toks), cache, jnp.asarray(table),
            jnp.int32(start), jnp.asarray([start + n_real], jnp.int32),
            calibrate=False)
    return np.asarray(logits[0, n_real - 1].astype(jnp.float32))


def compiled_texts(eng):
    """HLO text of the engine's compiled prefill-chunk and decode-block
    programs at the shapes it serves."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_paged_cache
    B, C, n_pp = eng.max_batch, eng.prefill_chunk, eng.n_pages_per_seq
    cache = jax.eval_shape(lambda: init_paged_cache(
        eng.cfg, eng.kv_manager.n_pages, eng.page_size, eng.opts))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    prefill = eng._prefill_chunk.lower(
        eng.params, i32(1, C), cache, i32(1, n_pp), i32(), i32(1),
        calibrate=False).compile().as_text()
    decode = eng._decode_fused.lower(
        eng.params, i32(B), i32(B), i32(B, n_pp), cache,
        n_steps=eng.decode_lookahead,
        done=jax.ShapeDtypeStruct((B,), jnp.bool_),
        quota=i32(B)).compile().as_text()
    return prefill, decode


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def one_chip(cfg, seed: int, counter: CompileCounter, dev) -> None:
    import numpy as np
    reqs = make_requests(cfg.vocab, seed)
    say(f"workload: {len(reqs)} requests, prompt lens {list(PROMPT_LENS)}, "
        f"shared prefix {PREFIX}, new tokens {NEW_TOKENS}, "
        f"kv_fast_mb {KV_FAST_MB}")
    logits = {}
    params = None
    for impl in ("xla", "pallas"):
        t0 = time.perf_counter()
        eng = build_engine(cfg, impl, seed=seed, params=params)
        params = eng.params          # the same weights for both paths
        say(f"{impl}: engine built in {time.perf_counter() - t0:.3f}s "
            f"(n_pages={eng.n_pages}, page_bytes={eng.page_nbytes})")
        serve(eng, reqs, impl, counter)
        logits[impl] = last_position_logits(eng, reqs[0])
        check(bool(np.isfinite(logits[impl]).all()),
              f"{impl}: non-finite logits")
        if impl == "pallas":
            prefill, decode = compiled_texts(eng)
            for name, text in (("prefill-chunk", prefill),
                               ("decode-block", decode)):
                n = text.count("tpu_custom_call")
                check(n > 0, f"pallas {name} program has no Pallas kernel")
                say(f"pallas: {name} program holds {n} tpu_custom_call "
                    f"site(s)")
        say(f"{impl}: peak_bytes_in_use={peak_bytes(dev)} "
            f"{counter.line()}")
    ref = np.abs(logits["xla"]).max()
    diff = np.abs(logits["xla"] - logits["pallas"]).max()
    agree = int(np.argmax(logits["xla"]) == np.argmax(logits["pallas"]))
    say(f"logits (prompt 0, last position): max_abs_xla={ref} "
        f"max_abs_diff={diff} rel={diff / ref} tolerance={LOGIT_RTOL} "
        f"argmax_agree={agree}")
    check(diff <= LOGIT_RTOL * ref,
          f"xla and pallas logits differ by {diff} > {LOGIT_RTOL} * {ref}")


def four_chips(cfg, seed: int, counter: CompileCounter, devices) -> None:
    reqs = make_requests(cfg.vocab, seed)
    outs = {}
    params = None
    for shards in (1, 4):
        eng = build_engine(cfg, "pallas", seed=seed, shards=shards,
                           params=params)
        params = eng.params          # the same weights for both serves
        outs[shards] = serve(eng, reqs, f"shards={shards}", counter)
        if shards == 4:
            k = eng.pool["stack"]["k"]
            placed = {s.device for s in k.addressable_shards}
            heads = {s.data.shape[2] for s in k.addressable_shards}
            check(placed == set(devices[:4]),
                  f"KV pool sits on {placed}, not on the first 4 devices")
            check(heads == {cfg.n_kv_heads // 4},
                  f"per-device KV head slices {heads}, want "
                  f"{cfg.n_kv_heads // 4}")
            say(f"shards=4: pool {tuple(k.shape)} split over "
                f"{len(placed)} devices, {heads.pop()} KV heads each")
    for i, d in enumerate(devices[:4]):
        say(f"device {i}: peak_bytes_in_use={peak_bytes(d)}")
    same = [outs[4][i] == outs[1][i] for i in range(len(reqs))]
    say(f"greedy tokens identical per request: {same}")
    check(all(same), "shards=4 and shards=1 emitted different tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: XLA vs Pallas on one chip; 4: head-sharded "
                         "serve vs one device")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the prompts")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={use_compile_cache()}")
    counter = CompileCounter()
    t0 = time.perf_counter()
    cfg = model_config()
    if args.chips == 4:
        four_chips(cfg, args.seed, counter, devices)
    else:
        one_chip(cfg, args.seed, counter, dev)
    say(f"total_s={time.perf_counter() - t0:.3f} {counter.line()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
