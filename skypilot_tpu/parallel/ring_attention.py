"""Ring attention: exact context parallelism over a mesh axis.

Long-context capability the reference lacks entirely (SURVEY.md §2.15: no
SP/CP/ring-attention anywhere in core or recipes).  Sequence is sharded over
the ``fsdp`` mesh axis; each step every device computes block attention of
its local Q against the K/V shard it currently holds, accumulates with
online-softmax statistics, then rotates K/V one hop around the ring with
`jax.lax.ppermute` — the collective rides ICI neighbor links, overlapping
with compute under XLA's async collectives.  Memory per device is O(S/n),
enabling sequences n× longer than one chip's HBM allows.

Matches the blockwise-parallel-transformer / RingAttention construction
(Liu et al.), built on `jax.shard_map` so it composes with the data/tensor
axes of the same mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, k_pos, causal):
    """Partial attention of local q against one K/V shard.

    Returns (numerator [B,H,Sq,D] f32, rowmax [B,H,Sq] f32,
    denominator [B,H,Sq] f32) — the online-softmax triple for later
    combination.  Positions are absolute, so causal masking is correct for
    arbitrary shard rotation.
    """
    scale = q.shape[-1]**-0.5
    b, h_q, s_q, d = q.shape
    h_kv = k.shape[1]
    group = h_q // h_kv
    # GQA as `ops/attention.py mha_reference` has it: the `group` query
    # heads of a kv head are `group * Sq` query rows of it, contracted
    # against K and V as they are stored; the mask is tiled over the group.
    q = q.reshape(b, h_kv, group * s_q, d)
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None, :, None] >= k_pos[:, None, None, :]
        s = jnp.where(jnp.tile(mask, (1, 1, group, 1)), s, _NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,Hkv,g*Sq]
    p = jnp.exp(s - m[..., None])
    # Fully-masked rows: m = NEG_INF → p = exp(0) = 1 per column, which is
    # wrong; zero them via the l=0 signal instead.
    p = jnp.where(m[..., None] <= _NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)                                   # [B,Hkv,g*Sq]
    num = jnp.einsum('bhqk,bhkd->bhqd', p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (num.reshape(b, h_q, s_q, d), m.reshape(b, h_q, s_q),
            l.reshape(b, h_q, s_q))


def _combine(acc, num, m_acc, m_blk, l_acc, l_blk):
    """Merge one block's online-softmax triple into the accumulator."""
    m_new = jnp.maximum(m_acc, m_blk)
    c_acc = jnp.exp(m_acc - m_new)
    c_blk = jnp.exp(m_blk - m_new)
    acc = acc * c_acc[..., None] + num * c_blk[..., None]
    l_new = l_acc * c_acc + l_blk * c_blk
    return acc, m_new, l_new


def _ring_attention_sharded(q, k, v, *, axis_name: str, causal: bool):
    """Body run per device under shard_map.  q/k/v: local shards
    [B, H, S_local, D] (kv possibly fewer heads)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    q_pos = (idx * s_local + jnp.arange(s_local))[None, :]    # [1, Sq]

    m0 = jnp.full(q.shape[:3], _NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)

    def step(t, carry):
        acc, m_acc, l_acc, k_cur, v_cur = carry
        # Chunk index currently held: started at idx, rotated t hops.
        kv_idx = (idx - t) % n
        k_pos = (kv_idx * s_local + jnp.arange(s_local))[None, :]
        num, m_blk, l_blk = _block_attend(q, k_cur, v_cur, q_pos, k_pos,
                                          causal)
        acc, m_acc, l_acc = _combine(acc, num, m_acc, m_blk, l_acc, l_blk)
        # Rotate K/V to the next device (ring over ICI neighbors).
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m_acc, l_acc, k_nxt, v_nxt

    acc, m_acc, l_acc, _, _ = jax.lax.fori_loop(
        0, n, step, (acc0, m0, l0, k, v))
    safe_l = jnp.where(l_acc == 0.0, 1.0, l_acc)
    return (acc / safe_l[..., None]).astype(q.dtype)


@functools.partial(jax.jit,
                   static_argnames=('mesh', 'axis_name', 'causal'))
def ring_attention(q: jax.Array,
                   k: jax.Array,
                   v: jax.Array,
                   mesh: Mesh,
                   axis_name: str = 'fsdp',
                   causal: bool = True) -> jax.Array:
    """Exact attention over sequences sharded on `axis_name`.

    q [B,Hq,S,D], k/v [B,Hkv,S,D] with S sharded over the axis; output has
    the same sharding as q.  Other mesh axes pass through unchanged (batch
    on 'data', heads on 'tensor').
    """
    spec_q = P(None, 'tensor', axis_name, None)
    fn = jax.shard_map(
        functools.partial(_ring_attention_sharded, axis_name=axis_name,
                          causal=causal),
        mesh=mesh,
        in_specs=(spec_q, spec_q, spec_q),
        out_specs=spec_q,
        check_vma=False,
    )
    return fn(q, k, v)
