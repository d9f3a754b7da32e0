"""Head-sharded execution of the paged attention kernels (DESIGN.md SS16).

One mesh axis ("model") partitions the KV-head dimension of the paged
pool. Each device runs the UNCHANGED single-device kernel over its own
Hkv/N head slice: the Pallas grids iterate (batch, kv_head, page) and
their scalar-prefetch index_maps only dereference the page table —
which replicates — so per-shard the kernels need no new index math.
Query heads partition in the same contiguous blocks (H/N = (Hkv/N) *
group, so the GQA group structure survives slicing), and the page
table / sequence lengths / window starts replicate: every shard attends
over the SAME pages, only the head slice differs.

The per-shard head outputs are all-gathered (tiled) back into full head
order before the replicated output projection. Per-head attention is
arithmetically independent and the gather restores exact head order, so
the sharded result is bitwise identical to the unsharded one — the
property the engine's token-identity acceptance leans on. (Sharding the
qkv/wo matmuls instead would reorder their reductions and break bitwise
equality; they stay replicated on purpose.)
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


AXIS = "model"                    # the KV-head mesh axis


def head_shards(mesh, n_kv_heads: int) -> int:
    """Usable shard count: the mesh's "model" extent when it divides the
    KV-head count, else 0 (callers fall back to the replicated path)."""
    if mesh is None:
        return 0
    shape = getattr(mesh, "shape", None)
    if not shape or AXIS not in shape:
        return 0
    n = shape[AXIS]
    return n if n > 1 and n_kv_heads % n == 0 else 0


def _spec(ndim: int, shard_axis=None) -> P:
    s = [None] * ndim
    if shard_axis is not None:
        s[shard_axis] = AXIS
    return P(*s)


def sharded_attend(mesh, attend, q, k_pages, v_pages, k_scale, v_scale,
                   extras, *, q_head_axis: int):
    """Run ``attend`` — any per-head paged attention body — head-sharded.

    q partitions on ``q_head_axis``; k_pages/v_pages on axis 1 (pools
    are head-major, (n_pages, Hkv, page_size, dh)); the (Hkv,) scales on
    their only axis; every array in ``extras`` (page table, lengths,
    window starts) replicates. ``attend(q, kp, vp, ksc, vsc, *extras)``
    runs once per shard on the local head slice and must return a tensor
    of q's rank with ``q_head_axis`` as its head dim; slices are
    all-gathered (tiled) back into full head order and returned
    replicated.
    """
    in_specs = (_spec(q.ndim, q_head_axis), _spec(k_pages.ndim, 1),
                _spec(v_pages.ndim, 1), P(AXIS), P(AXIS))
    in_specs += tuple(_spec(e.ndim) for e in extras)

    def body(q_l, kp_l, vp_l, ks_l, vs_l, *ex):
        out = attend(q_l, kp_l, vp_l, ks_l, vs_l, *ex)
        return jax.lax.all_gather(out, AXIS, axis=q_head_axis, tiled=True)

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=_spec(q.ndim), check_vma=False)
    return fn(q, k_pages, v_pages, k_scale, v_scale, *extras)
