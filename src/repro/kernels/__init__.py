"""Pallas TPU kernels for the paper's compute hot-spots.

flash_attention  — blocked causal/GQA prefill attention (VMEM tiling)
decode_attention — memory-bound KV-cache attention (bf16/int8 KV): the
                   paper's dominant decode kernel, with the int8 variant
                   realizing its "shrink attention traffic" insight on TPU,
                   plus a paged variant that gathers physical KV pages via a
                   scalar-prefetched page table (continuous batching)
ops              — the entries the model calls: eligibility rules that
                   raise on an ineligible shape, interpret mode on CPU
ref              — pure-jnp oracles
"""
from repro.kernels import decode_attention, flash_attention, ops, ref
from repro.kernels.decode_attention import paged_decode_attention, quantize_kv

__all__ = ["decode_attention", "flash_attention", "ops", "ref",
           "paged_decode_attention", "quantize_kv"]
