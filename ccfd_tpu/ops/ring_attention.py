"""Ring attention: exact attention over sequence-sharded inputs.

The reference has no sequence dimension (SURVEY.md §5 "long-context: N/A"),
but this framework treats long-context as first-class: the sequence scorer
(ccfd_tpu/models/seq.py) attends over per-customer transaction histories,
and histories longer than one chip's memory shard over the mesh. Ring
attention computes *exact* softmax attention with the sequence dimension
sharded: each device keeps its Q shard resident and rotates K/V shards
around the ring with ``lax.ppermute`` (ICI neighbor hops, no all-gather),
accumulating the softmax online (flash-attention style running max /
denominator), so peak memory per device is O(L_local) regardless of total
sequence length.

Implemented with ``shard_map`` over a named mesh axis; the per-device body
is a ``lax.scan`` of (blockwise attention + ppermute), fully compiled — no
host round-trips per ring step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _online_block(q, k_blk, v_blk, m, l, o):
    """One blockwise-attention accumulation step (numerically stable).

    q: (B, H, Lq, D); k_blk/v_blk: (B, H, Lk, D);
    m: (B, H, Lq) running max; l: (B, H, Lq) running denom;
    o: (B, H, Lq, D) running numerator.
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk, preferred_element_type=jnp.float32)
    s = s * scale.astype(jnp.float32)
    m_new = jnp.maximum(m, s.max(axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
                    preferred_element_type=jnp.float32)
    o_new = o * correction[..., None] + pv
    return m_new, l_new, o_new


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Plain full attention (B, H, L, D) — the single-device reference."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def _ring_body(q, k, v, axis_name: str):
    """Per-device program: accumulate over all ring positions."""
    n = jax.lax.psum(1, axis_name)
    batch, heads, lq, d = q.shape
    m0 = jnp.full((batch, heads, lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((batch, heads, lq), jnp.float32)
    o0 = jnp.zeros((batch, heads, lq, d), jnp.float32)
    # the accumulators become device-varying after one step; mark the scan
    # carry as varying over the ring axis up front (shard_map scan-vma rule)
    m0, l0, o0 = (jax.lax.pcast(t, (axis_name,), to="varying")
                  for t in (m0, l0, o0))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        k_blk, v_blk, m, l, o = carry
        m, l, o = _online_block(q, k_blk, v_blk, m, l, o)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), None

    # n-1 (accumulate + rotate) steps, then a final accumulate with no
    # rotation — the last permute's output would never be consumed and each
    # skipped ppermute saves ICI traffic in forward AND backward.
    (k, v, m, l, o), _ = jax.lax.scan(step, (k, v, m0, l0, o0), None, length=n - 1)
    m, l, o = _online_block(q, k, v, m, l, o)
    return (o / l[..., None]).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str,
) -> jax.Array:
    """Exact attention with L sharded over ``axis_name``. (B, H, L, D) in/out.

    L must divide evenly by the axis size. Non-causal (transaction histories
    attend bidirectionally).
    """
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(_ring_body, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
