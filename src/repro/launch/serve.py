"""Serving CLI: batched greedy decode with the tiered-KV policy.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
        --batch 4 --prompt-len 32 --new-tokens 32 --kv-policy int8

Continuous batching over the paged KV pool (ragged prompts, per-step
join/retire, page-pool preemption):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
        --scheduler continuous --concurrency 8 --page-size 16
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models import RuntimeOptions
from repro.serving import ServeEngine
from repro.serving.trace import HOST_PHASES, LAYER_SPANS


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--kv-policy", default="native",
                    choices=["native", "int8"])
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--scheduler", default="static",
                    choices=["static", "continuous"])
    ap.add_argument("--concurrency", type=int, default=0,
                    help="number of in-flight ragged requests "
                         "(0: one equal-length wave of --batch prompts)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="continuous scheduler slot count")
    ap.add_argument("--shards", type=int, default=1,
                    help="head-shard the paged KV pool and attention "
                         "kernels over this many devices (DESIGN.md SS16; "
                         "requires --scheduler continuous and "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N on the CPU rig)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="serialize the prefill and decode streams onto "
                         "one virtual queue (the pre-SS16 loop) instead "
                         "of overlapping them")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="tokens per prefill chunk (continuous scheduler; "
                         "default 2 pages, min 32)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prefill tokens per engine step")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix KV page reuse")
    ap.add_argument("--decode-lookahead", type=int, default=8,
                    help="fused decode block size K: sample greedily on "
                         "device and sync with the host once per K tokens "
                         "instead of once per token; KV pages for the K "
                         "writes are reserved ahead (all-or-nothing). K=1 "
                         "reproduces the per-token loop exactly; any K is "
                         "token-identical (default: 8)")
    ap.add_argument("--spec-mode", default="off",
                    choices=["off", "ngram", "model"],
                    help="speculative decoding on the fused paged path "
                         "(DESIGN.md SS14): 'ngram' drafts by prompt "
                         "lookup (model-free), 'model' drafts with a small "
                         "paged-KV model (--draft-config); requires "
                         "--scheduler continuous")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens verified per pass (the verify "
                         "window is K+1 wide; acceptance-adaptive per "
                         "request)")
    ap.add_argument("--draft-config",
                    help="arch name for the --spec-mode model draft "
                         "(reduced with --d-model/2 when --reduced)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0: greedy). Stochastic "
                         "sampling runs on device from per-request seeded "
                         "keys; with spec decoding, leftover/rejection "
                         "sampling keeps the output distribution exact")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k logit filter (0: off; needs --temperature)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus filter (1.0: off; needs --temperature)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed for per-request sampling keys")
    ap.add_argument("--shared-doc", type=int, default=0,
                    help="prepend a shared document of this many tokens to "
                         "every request (exercises prefix dedup)")
    ap.add_argument("--kv-fast-mb", type=float, default=None,
                    help="cap the fast KV tier (DDR) at this many MB and "
                         "offload the overflow to simulated HBS "
                         "(DESIGN.md SS13); enables real page residency, "
                         "spill/prefetch, and stall accounting")
    ap.add_argument("--hbs-gb", type=float, default=64.0,
                    help="HBS offload tier capacity in GB")
    ap.add_argument("--hbs-gbps", type=float, default=None,
                    help="override HBS bandwidth (GB/s) for migration "
                         "timing (default: the hierarchy preset's)")
    ap.add_argument("--hbs-us", type=float, default=None,
                    help="override HBS issue latency (µs) for migration "
                         "timing")
    ap.add_argument("--chiplet-mb", type=float, default=None,
                    help="bond a promote-only SRAM chiplet buffer of this "
                         "many MB in front of the fast KV tier (DESIGN.md "
                         "SS17); hot pages promote in by EMA touch "
                         "frequency, cold residents demote out LRU "
                         "(needs --kv-fast-mb)")
    ap.add_argument("--chiplet-gbps", type=float, default=None,
                    help="override the chiplet link bandwidth (GB/s) for "
                         "promotion/demotion timing (default: the "
                         "sram_chiplet preset's)")
    ap.add_argument("--chiplet-us", type=float, default=None,
                    help="override the chiplet link issue latency (µs)")
    ap.add_argument("--layer-overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="slice each demand fetch per layer and pipeline "
                         "the slices against the kernel's layer loop "
                         "(DESIGN.md SS17); --no-layer-overlap restores "
                         "the whole-block fetch barrier baseline")
    ap.add_argument("--writeback-link", default="dedicated",
                    choices=["shared", "dedicated"],
                    help="'dedicated': dirty-page write-back rides its own "
                         "out channel; 'shared': spills and fetches "
                         "contend for one half-duplex offload link")
    ap.add_argument("--trace-out", default=None,
                    help="write the run's Chrome trace-event JSON here "
                         "(perfetto-loadable: one track per request plus "
                         "engine/DMA tracks on the virtual clock; "
                         "continuous scheduler only — DESIGN.md SS15)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT target: print the goodput report (requests "
                         "meeting SLO + per-phase blame for violators)")
    ap.add_argument("--slo-itl-ms", type=float, default=None,
                    help="per-request p95 inter-token-latency target for "
                         "the goodput report")
    args = ap.parse_args()
    use_compile_cache()
    wants_trace = (args.trace_out or args.slo_ttft_ms is not None
                   or args.slo_itl_ms is not None)
    if wants_trace and args.scheduler != "continuous":
        ap.error("--trace-out/--slo-* need --scheduler continuous (the "
                 "trace recorder instruments the continuous engine)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, d_model=args.d_model)
    draft_cfg = None
    if args.draft_config:
        draft_cfg = get_config(args.draft_config)
        if args.reduced:
            draft_cfg = reduced(draft_cfg, d_model=max(args.d_model // 2, 16))
    max_len = args.prompt_len + args.new_tokens + args.shared_doc
    hier = None
    if args.chiplet_mb is not None and args.kv_fast_mb is None:
        ap.error("--chiplet-mb needs --kv-fast-mb (the chiplet promotes "
                 "out of the tiered KV pool)")
    if args.kv_fast_mb is not None:
        from repro.core import hbs, lpddr6, npu_hierarchy, sram_chiplet
        chiplet = None
        if args.chiplet_mb is not None:
            chiplet = sram_chiplet(args.chiplet_gbps or 512.0,
                                   capacity_mb=args.chiplet_mb)
        hier = npu_hierarchy(
            lpddr6(capacity_gb=args.kv_fast_mb / 1e3),
            hbs(args.hbs_gbps or 8.0, latency_us=args.hbs_us or 20.0,
                capacity_gb=args.hbs_gb),
            chiplet=chiplet)
    eng = ServeEngine(cfg, opts=RuntimeOptions(dtype=args.dtype),
                      kv_policy=args.kv_policy, max_len=max_len,
                      scheduler=args.scheduler, page_size=args.page_size,
                      max_batch=args.max_batch,
                      prefill_chunk=args.prefill_chunk,
                      prefill_budget=args.prefill_budget,
                      prefix_cache=not args.no_prefix_cache,
                      decode_lookahead=args.decode_lookahead,
                      hierarchy=hier, hbs_gbps=args.hbs_gbps,
                      hbs_latency_us=args.hbs_us,
                      spec_mode=args.spec_mode, spec_k=args.spec_k,
                      draft_cfg=draft_cfg, temperature=args.temperature,
                      top_k=args.top_k, top_p=args.top_p,
                      sample_seed=args.seed,
                      shards=args.shards, overlap=not args.no_overlap,
                      chiplet_gbps=args.chiplet_gbps,
                      chiplet_latency_us=args.chiplet_us,
                      layer_overlap=args.layer_overlap,
                      writeback_link=args.writeback_link)

    rng = np.random.default_rng(0)
    if args.concurrency:
        # ragged request stream: lengths in [prompt_len // 2, prompt_len]
        doc = rng.integers(1, cfg.vocab, size=args.shared_doc).tolist()
        lens = rng.integers(max(args.prompt_len // 2, 1),
                            args.prompt_len + 1, size=args.concurrency)
        reqs = [doc + rng.integers(1, cfg.vocab, size=n).tolist()
                for n in lens]
        outs = eng.serve(reqs, args.new_tokens)
    else:
        prompts = jax.random.randint(jax.random.PRNGKey(0),
                                     (args.batch, args.prompt_len), 1,
                                     cfg.vocab)
        if args.scheduler == "continuous":
            # route through the configured scheduler, not the static wave
            outs = eng.serve([row.tolist() for row in np.asarray(prompts)],
                             args.new_tokens)
        else:
            outs = eng.generate(jnp.asarray(prompts), args.new_tokens)
    s = eng.stats
    print(f"[serve] arch={cfg.name} sched={args.scheduler} "
          f"kv={args.kv_policy} reqs={s.requests} "
          f"prefill={s.prefill_s*1e3:.0f}ms decode={s.decode_s*1e3:.0f}ms "
          f"serve={s.serve_s*1e3:.0f}ms "
          f"steps={s.decode_steps} lookahead={args.decode_lookahead} "
          f"syncs={s.host_syncs} preempt={s.preemptions} TPS={s.tps:.1f} "
          f"shards={args.shards} overlap={not args.no_overlap}")
    if args.scheduler == "continuous":
        print(f"[serve] prefill_toks={s.prefill_tokens_computed} "
              f"cached={s.cached_prefix_tokens} deduped={s.pages_deduped} "
              f"cow={s.cow_copies} compiles={s.prefill_compiles} "
              f"ttft_p50/p95={s.ttft_p50*1e3:.1f}/{s.ttft_p95*1e3:.1f}ms "
              f"itl_p50/p95={s.itl_p50*1e3:.1f}/{s.itl_p95*1e3:.1f}ms")
        if hier is not None:
            # peak KV footprint priced at the ACTIVE cache width (an int8
            # pool is 1 B/elem, not bf16's 2 — DESIGN.md SS13)
            peak_mb = s.peak_pages_used * eng.page_nbytes / 1e6
            fast_mb = s.peak_fast_pages * eng.page_nbytes / 1e6
            print(f"[serve] offload: stall={s.stall_s*1e3:.1f}ms "
                  f"spilled={s.pages_spilled}p/{s.spill_bytes/1e6:.2f}MB "
                  f"fetched={s.pages_fetched}p/{s.fetch_bytes/1e6:.2f}MB "
                  f"prefetch_hit={s.prefetch_hit_rate:.0%} "
                  f"kv_width={eng.kv_dtype_bytes}B "
                  f"peak_kv={peak_mb:.2f}MB (fast {fast_mb:.2f}MB)")
            print(f"[serve] overlap: layer_overlap={args.layer_overlap} "
                  f"stall_saved={s.stall_saved_s*1e3:.1f}ms "
                  f"writeback={args.writeback_link} "
                  f"clean_demotions={s.clean_demotions}")
            if args.chiplet_mb is not None:
                chan = " ".join(f"{k}={v/1e6:.2f}MB" for k, v
                                in sorted(s.channel_bytes.items()))
                print(f"[serve] chiplet: {args.chiplet_mb:g}MB "
                      f"hit_rate={s.chiplet_hit_rate:.0%} "
                      f"promoted={s.chiplet_promotions}p "
                      f"demoted={s.chiplet_demotions}p "
                      f"channels[{chan}]")
            if s.stall_by_rid:
                worst = sorted(s.stall_by_rid.items(),
                               key=lambda kv_: -kv_[1])[:4]
                per = " ".join(f"r{r}={v*1e3:.1f}ms" for r, v in worst)
                print(f"[serve] stall by request (top): {per}")
        if args.spec_mode != "off":
            print(f"[serve] spec: mode={args.spec_mode} k={args.spec_k} "
                  f"blocks={s.spec_blocks} proposed={s.draft_proposed} "
                  f"accepted={s.draft_accepted} "
                  f"accept_rate={s.acceptance_rate:.0%}")
        # ---- structured trace exports (DESIGN.md SS15) ---- #
        if eng.trace is not None:
            agg = eng.trace.aggregate_breakdown_ms()
            print("[serve] time breakdown: " + " ".join(
                f"{p}={agg[f'{p}_ms']:.1f}ms"
                for p in ("queue", "prefill", "recompute", "decode",
                          "stall", "draft")))
            # the same serve on the host's wall clock
            tr = eng.trace
            print("[serve] host phases: " + " ".join(
                f"{p}={tr.host_s[p]*1e3:.1f}ms/{tr.host_n[p]}"
                for p in HOST_PHASES + LAYER_SPANS if p in tr.host_s))
            print("[serve] compiled during serve: " + (" ".join(
                f"{k}={n}" for k, n in sorted(tr.compiles.items()))
                or "none"))
            w = tr.wall_summary_ms()
            print(f"[serve] wall ttft_p50/p90={w['ttft_p50_ms']:.1f}/"
                  f"{w['ttft_p90_ms']:.1f}ms queue_p50/p90="
                  f"{w['queue_p50_ms']:.1f}/{w['queue_p90_ms']:.1f}ms")
            if args.slo_ttft_ms is not None or args.slo_itl_ms is not None:
                rep = eng.trace.slo_report(
                    None if args.slo_ttft_ms is None
                    else args.slo_ttft_ms * 1e-3,
                    None if args.slo_itl_ms is None
                    else args.slo_itl_ms * 1e-3)
                print(f"[serve] goodput: {rep['n_met_slo']}/"
                      f"{rep['n_requests']} met SLO "
                      f"(frac={rep['goodput_frac']:.2f})")
                for v in rep["violators"][:6]:
                    print(f"[serve]   violator r{v['rid']}: "
                          f"ttft={v['ttft_ms']:.1f}ms "
                          f"itl_p95={v['itl_p95_ms']:.1f}ms "
                          f"blame={v['blame']}")
            if args.trace_out:
                eng.trace.save(args.trace_out)
                print(f"[serve] wrote trace {args.trace_out} "
                      f"(reconciled={eng.trace_report['ok']})")
    print("[serve] first output:", outs[0][:16])


if __name__ == "__main__":
    main()
