"""Ahead-of-time compiles of the served Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's tiling and
memory-space rules; the TPU compiler, which compiles for a described chip
without one attached, can. Each case lowers one kernel at llama3.2-1b
widths (32 query heads of 64; 8 KV heads as in the released model, or 32
as in ``configs/llama32_1b.py``) for one chip of a described ``v5e:2x2``
topology and checks that the kernel survived as a ``tpu_custom_call``
under its stable name (the ``pallas_call`` ``name=``, which a device
trace shows). One more case compiles the whole prefill chunk program at
qwen2.5-3b widths and checks that it writes the KV pool in place.
Nothing runs, so these say nothing about results or times.

The topology is described only inside the module fixture: the TPU library
admits one process at a time, and a description made at import time
would make pytest workers collect different tests.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

import repro.kernels.decode_attention as da
import repro.kernels.flash_attention as fa

H, HKV, DH = 32, 8, 64          # llama3.2-1b attention widths (GQA)
B = 8                           # decode slots
MAX_LEN = 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    # under a program of another name, as in the engine, so that only the
    # kernel's own name can name its instruction
    return jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()


def _named_kernel(text, name):
    """The compiled kernel is an instruction ``%<name>[.n] = ...
    custom-call(...)``: the name a device trace shows for it."""
    return re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call", text)


def _pool(page_size, dtype, n_seqs, n_kv_heads=HKV):
    n_pages = n_seqs * (MAX_LEN // page_size) + 1
    return ((n_pages, n_kv_heads, page_size, DH), dtype)


@pytest.mark.parametrize("kv_dtype,page_size,n_kv_heads", [
    (jnp.bfloat16, 16, HKV), (jnp.int8, 32, HKV), (jnp.bfloat16, 16, H)])
def test_paged_decode_compiles(one_chip, kv_dtype, page_size, n_kv_heads):
    npp = MAX_LEN // page_size
    quant = kv_dtype == jnp.int8

    def fn(q, kp, vp, pt, lens, ksc, vsc):
        return da.paged_decode_attention(
            q, kp, vp, pt, lens, k_scale=ksc if quant else None,
            v_scale=vsc if quant else None)
    text = _compile_text(
        fn, one_chip, ((B, H, DH), jnp.bfloat16),
        _pool(page_size, kv_dtype, B, n_kv_heads),
        _pool(page_size, kv_dtype, B, n_kv_heads),
        ((B, npp), jnp.int32), ((B,), jnp.int32),
        ((n_kv_heads,), jnp.float32), ((n_kv_heads,), jnp.float32))
    assert "tpu_custom_call" in text
    assert _named_kernel(text, "paged_decode_attention")


def test_chunk_prefill_compiles(one_chip):
    C, page_size = 32, 16
    npp = MAX_LEN // page_size
    text = _compile_text(
        da.chunk_prefill_attention, one_chip, ((1, C, H, DH), jnp.bfloat16),
        _pool(page_size, jnp.bfloat16, 1), _pool(page_size, jnp.bfloat16, 1),
        ((1, npp), jnp.int32), ((), jnp.int32), ((1,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _named_kernel(text, "chunk_prefill_attention")


def test_spec_verify_compiles(one_chip):
    C, page_size = 5, 16        # K = 4 drafts + the last committed token
    npp = MAX_LEN // page_size
    text = _compile_text(
        da.spec_verify_attention, one_chip, ((B, C, H, DH), jnp.bfloat16),
        _pool(page_size, jnp.bfloat16, B), _pool(page_size, jnp.bfloat16, B),
        ((B, npp), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in text
    # the verify pass runs the chunk-prefill kernel
    assert _named_kernel(text, "chunk_prefill_attention")


def test_flash_attention_compiles(one_chip):
    S = 1024
    text = _compile_text(
        fa.flash_attention, one_chip, ((1, S, H, DH), jnp.bfloat16),
        ((1, S, HKV, DH), jnp.bfloat16), ((1, S, HKV, DH), jnp.bfloat16))
    assert "tpu_custom_call" in text
    assert _named_kernel(text, "flash_attention")


# ----------------- the prefill chunk program, whole ---------------------- #

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
_HEADER = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")


def _instructions(text):
    """(computation, name, dims, opcode, called computation) of every
    array-valued instruction, and each computation's ROOT opcode."""
    rows, roots, comp = [], {}, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h:
            comp = h.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        calls = re.search(r"calls=%([^,\s]+)", line)
        rows.append((comp, m.group(1), dims, m.group(3),
                     calls.group(1) if calls else None))
        if line.lstrip().startswith("ROOT "):
            roots[comp] = m.group(3)
    return rows, roots


def test_prefill_chunk_program_writes_pool_in_place(one_chip):
    """The prefill chunk program at qwen2.5-3b widths (two layers, a pool
    of 1025 pages, a 32-token chunk) carries the pool through its layer
    scan and writes whole pages into it in place. So no temporary is as
    large as one layer of the pool, and no instruction results in the
    pool's or a layer's shape but the parameters, the scan's tuple
    elements and the scatter into the carried pool: a copy, a
    dynamic-slice or dynamic-update-slice, or a relayout there would be
    the compiler copying the pool again."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import RuntimeOptions, init_params
    from repro.models.lm import init_paged_cache, prefill_paged_chunk
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), n_layers=2)
    opts = RuntimeOptions(dtype="bfloat16")
    n_pages, ps, C = 1025, 16, 32

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), opts)))
    cache = shapes(jax.eval_shape(
        lambda: init_paged_cache(cfg, n_pages, ps, opts)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, c, pt, s, n: prefill_paged_chunk(cfg, p, t, c, pt, s,
                                                      n, opts),
        donate_argnums=(2,)).lower(
            params, i32(1, C), cache, i32(1, MAX_LEN // ps), i32(),
            i32(1)).compile()

    page = (cfg.n_kv_heads, ps, cfg.head_dim)
    layer_bytes = 2 * n_pages * math.prod(page)       # bf16
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes

    def pool_sized(dims):
        d = [x for x in dims if x != 1]
        if len(d) == 5 and d[0] == cfg.n_layers:
            d = d[1:]
        return sorted(d) == sorted((n_pages,) + page)
    rows, roots = _instructions(compiled.as_text())
    writes = [r for r in rows if pool_sized(r[2]) and (
        r[3] == "scatter" or (r[3] == "fusion" and roots[r[4]] == "scatter"))]
    others = [r for r in rows if pool_sized(r[2]) and r not in writes
              and r[3] not in ("parameter", "get-tuple-element")]
    assert writes, "no scatter into the carried pool"
    assert not others, others
