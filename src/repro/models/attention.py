"""Attention: GQA + RoPE + sliding-window + softcap + caches, flash-style.

Memory discipline: no (S x S) score matrix is ever materialised. Prefill and
training run a two-level chunked online-softmax (outer scan over query chunks,
inner scan over KV chunks) — the pure-JAX flash-attention pattern, which keeps
the peak live intermediate at (b, heads, q_chunk, kv_chunk).

Sliding-window layers use a *banded* inner loop: each query chunk slices only
the (window + q_chunk) span of KV it can see, so window attention lowers to
O(S*W) FLOPs, not O(S^2) masked.

Causal full attention is masked-full by default (2x score FLOPs — honest
baseline; see EXPERIMENTS.md §Perf for the banded variant).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models.layers import COMPUTE_DTYPE, _normal, apply_rope, softcap

Array = jax.Array
NEG = -1e30  # mask value (avoid nan from -inf - -inf)


def init_attention(rng, d: int, n_heads: int, n_kv: int, head_dim: int):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    std = 1.0 / math.sqrt(d)
    return {
        "wq": _normal(k1, (d, n_heads, head_dim), std),
        "wk": _normal(k2, (d, n_kv, head_dim), std),
        "wv": _normal(k3, (d, n_kv, head_dim), std),
        "wo": _normal(k4, (n_heads, head_dim, d), 1.0 / math.sqrt(n_heads * head_dim)),
    }


def _qkv(params, x: Array, n_kv: int):
    xc = x.astype(COMPUTE_DTYPE)
    q = jnp.einsum("bsd,dhk->bshk", xc, params["wq"].astype(COMPUTE_DTYPE))
    k = jnp.einsum("bsd,dhk->bshk", xc, params["wk"].astype(COMPUTE_DTYPE))
    v = jnp.einsum("bsd,dhk->bshk", xc, params["wv"].astype(COMPUTE_DTYPE))
    q = shard_act(q, "batch", None, "heads", "head_dim")
    k = shard_act(k, "batch", None, "kv_heads", "head_dim")
    v = shard_act(v, "batch", None, "kv_heads", "head_dim")
    return q, k, v


def _out(params, o: Array) -> Array:
    return jnp.einsum("bshk,hkd->bsd", o.astype(COMPUTE_DTYPE),
                      params["wo"].astype(COMPUTE_DTYPE))


# ---------------------------------------------------------------------------
# Chunked online-softmax core
# ---------------------------------------------------------------------------

def _chunk_scores(q, ks, scale, cap):
    """q: (b, qc, KV, g, dh); ks: (b, kc, KV, dh) -> (b, KV, g, qc, kc) f32."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(COMPUTE_DTYPE),
                   ks.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32)
    return softcap(s * scale, cap)


def _online_block(q, k, v, q_pos, kv_pos, *, scale, cap, causal, window,
                  kv_chunk):
    """Attend q chunk over the whole given k/v with an inner online scan.

    q: (b, qc, KV, g, dh); k, v: (b, skv, KV, dh);
    q_pos: (qc,) absolute; kv_pos: (skv,) absolute (-1 = invalid slot).
    Returns (b, qc, KV, g, dh) f32 output.
    """
    b, qc, KV, g, dh = q.shape
    skv = k.shape[1]
    nkc = skv // kv_chunk

    def body(carry, idx):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, idx * kv_chunk, kv_chunk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, idx * kv_chunk, kv_chunk, 1)
        kp = jax.lax.dynamic_slice_in_dim(kv_pos, idx * kv_chunk, kv_chunk, 0)
        s = _chunk_scores(q, ks, scale, cap)           # (b, KV, g, qc, kc)
        ok = kp[None, :] >= 0
        if causal:
            ok = ok & (q_pos[:, None] >= kp[None, :])
        if window > 0:
            ok = ok & (q_pos[:, None] - kp[None, :] < window)
        s = jnp.where(ok[None, None, None, :, :], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(COMPUTE_DTYPE),
                         vs.astype(COMPUTE_DTYPE),
                         preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + upd
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, KV, g, qc), NEG, jnp.float32)
    l0 = jnp.zeros((b, KV, g, qc), jnp.float32)
    a0 = jnp.zeros((b, KV, g, qc, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nkc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4))          # (b, qc, KV, g, dh)


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      q_offset=0, scale: Optional[float] = None,
                      cap: Optional[float] = None, q_chunk: int = 512,
                      kv_chunk: int = 512, banded_causal: bool = False,
                      _no_seq_shard: bool = False) -> Array:
    """q: (b, sq, H, dh); k, v: (b, skv, KV, dh). Returns (b, sq, H, dh).

    ``window`` > 0 restricts attention to the last ``window`` positions and
    activates the banded KV slicing path (O(S*W) FLOPs).
    ``banded_causal`` activates per-q-chunk KV truncation for causal full
    attention (FLOP-exact, larger HLO; used by the §Perf variants).

    Sequence-parallel core: when the active AxisRules set
    ``attn_core_seq_shard`` (archs whose head count does not divide the TP
    axis), the core runs under shard_map with queries sequence-sharded over
    that axis and K/V replicated (cheap for GQA's few KV heads) — the exact
    context-parallel formulation, FLOPs split across the axis.
    """
    b, sq, H, dh = q.shape
    if not _no_seq_shard:
        from repro.distributed.sharding import current_rules
        from jax.sharding import PartitionSpec as P
        r = current_rules()
        ax = r.rules.get("attn_core_seq_shard") if (r and r.mesh) else None
        if ax is not None and not banded_causal:
            n_ax = dict(zip(r.mesh.axis_names, r.mesh.devices.shape))[ax]
            if sq > 1 and sq % n_ax == 0:
                dp = r.rules.get("batch")
                s_loc = sq // n_ax

                def local(qs, ks, vs):
                    idx = jax.lax.axis_index(ax)
                    return chunked_attention(
                        qs, ks, vs, causal=causal, window=window,
                        q_offset=q_offset + idx * s_loc, scale=scale, cap=cap,
                        q_chunk=min(q_chunk, s_loc), kv_chunk=kv_chunk,
                        _no_seq_shard=True)

                return jax.shard_map(
                    local, mesh=r.mesh,
                    in_specs=(P(dp, ax), P(dp), P(dp)),
                    out_specs=P(dp, ax), check_vma=False)(q, k, v)
    KV = k.shape[2]
    g = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    q = q.reshape(b, sq, KV, g, dh)

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, k.shape[1])
    # pad q/kv to chunk multiples (padded KV slots carry kv_pos = -1 -> masked)
    sq_orig, skv_orig = sq, k.shape[1]
    q_pad = (-sq) % q_chunk
    kv_pad = (-k.shape[1]) % kv_chunk
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0), (0, 0)))
        sq += q_pad
    kv_pos = jnp.arange(skv_orig, dtype=jnp.int32)
    if kv_pad:
        k = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        kv_pos = jnp.concatenate([kv_pos, jnp.full((kv_pad,), -1, jnp.int32)])
    nqc = sq // q_chunk

    if window > 0 and causal:
        # banded: each q chunk sees a fixed (window + q_chunk) KV span
        span = window + q_chunk
        span = min(int(math.ceil(span / kv_chunk)) * kv_chunk, k.shape[1])

        def q_body(_, i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, 1)
            q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk, dtype=jnp.int32)
            start = jnp.clip(q_offset + i * q_chunk + q_chunk - span, 0,
                             k.shape[1] - span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, span, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, span, 1)
            kp = start + jnp.arange(span, dtype=jnp.int32)
            o = _online_block(qs, ks, vs, q_pos, kp, scale=scale, cap=cap,
                              causal=True, window=window, kv_chunk=kv_chunk)
            return None, o

        _, outs = jax.lax.scan(q_body, None, jnp.arange(nqc))
    elif causal and banded_causal:
        # FLOP-exact causal: python loop, q chunk i scans only chunks <= i
        outs_list = []
        for i in range(nqc):
            qs = q[:, i * q_chunk:(i + 1) * q_chunk]
            q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk, dtype=jnp.int32)
            hi_chunk = min((q_offset + (i + 1) * q_chunk + kv_chunk - 1) // kv_chunk,
                           k.shape[1] // kv_chunk)
            hi = max(hi_chunk * kv_chunk, kv_chunk)
            o = _online_block(qs, k[:, :hi], v[:, :hi], q_pos, kv_pos[:hi],
                              scale=scale, cap=cap, causal=True, window=0,
                              kv_chunk=kv_chunk)
            outs_list.append(o)
        outs = jnp.stack(outs_list, axis=0)
    else:
        def q_body(_, i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, 1)
            q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk, dtype=jnp.int32)
            o = _online_block(qs, k, v, q_pos, kv_pos, scale=scale, cap=cap,
                              causal=causal, window=window, kv_chunk=kv_chunk)
            return None, o

        _, outs = jax.lax.scan(q_body, None, jnp.arange(nqc))

    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, H, dh)
    if sq != sq_orig:
        out = out[:, :sq_orig]
    return out.astype(COMPUTE_DTYPE)


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, n_kv: int, head_dim: int, max_len: int,
                  window: int = 0, dtype=COMPUTE_DTYPE):
    """window > 0 -> rolling buffer of size window (padded to 128)."""
    size = min(max_len, window) if window > 0 else max_len
    size = max(128, ((size + 127) // 128) * 128)
    size = min(size, max_len) if window == 0 else size
    return {
        "k": jnp.zeros((batch, size, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, size, n_kv, head_dim), dtype),
        "slot_pos": jnp.full((size,), -1, jnp.int32),  # absolute pos per slot
        "pos": jnp.zeros((), jnp.int32),               # next position
    }


def cache_update_prefill(cache, k, v):
    """Write a full prefill of length s at positions [0, s)."""
    s = k.shape[1]
    size = cache["k"].shape[1]
    if s >= size:  # keep the last `size` positions (rolling window case)
        ks, vs = k[:, s - size:], v[:, s - size:]
        pos = jnp.arange(s - size, s, dtype=jnp.int32)
        # store at slot = pos % size so decode writes continue seamlessly
        slots = pos % size
        order = jnp.argsort(slots)
        new = {
            "k": ks[:, order], "v": vs[:, order],
            "slot_pos": pos[order], "pos": jnp.asarray(s, jnp.int32),
        }
        return new
    nk = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, 1)
    nv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, 1)
    sp = cache["slot_pos"].at[:s].set(jnp.arange(s, dtype=jnp.int32))
    return {"k": nk, "v": nv, "slot_pos": sp, "pos": jnp.asarray(s, jnp.int32)}


def cache_update_decode(cache, k1, v1):
    """Append one position (k1, v1: (b, 1, KV, dh)) at slot pos % size."""
    size = cache["k"].shape[1]
    pos = cache["pos"]
    slot = pos % size
    nk = jax.lax.dynamic_update_slice_in_dim(cache["k"], k1.astype(cache["k"].dtype), slot, 1)
    nv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v1.astype(cache["v"].dtype), slot, 1)
    sp = jax.lax.dynamic_update_slice_in_dim(cache["slot_pos"],
                                             pos[None].astype(jnp.int32), slot, 0)
    return {"k": nk, "v": nv, "slot_pos": sp, "pos": pos + 1}


def decode_attend(q, cache, *, window: int = 0, scale=None, cap=None) -> Array:
    """Single-step attention over the cache. q: (b, 1, H, dh)."""
    b, sq, H, dh = q.shape
    k, v, slot_pos = cache["k"], cache["v"], cache["slot_pos"]
    KV = k.shape[2]
    g = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    pos = cache["pos"] - 1  # position of the query token
    qh = q.reshape(b, sq, KV, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh.astype(COMPUTE_DTYPE),
                   k.astype(COMPUTE_DTYPE), preferred_element_type=jnp.float32)
    s = softcap(s * scale, cap)
    ok = (slot_pos >= 0) & (slot_pos <= pos)
    if window > 0:
        ok = ok & (pos - slot_pos < window)
    s = jnp.where(ok[None, None, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(COMPUTE_DTYPE),
                   v.astype(COMPUTE_DTYPE))
    return o.reshape(b, sq, H, dh)


# ---------------------------------------------------------------------------
# Full attention blocks (train / prefill / decode)
# ---------------------------------------------------------------------------

def attn_forward(params, x, *, n_kv: int, causal: bool, window: int = 0,
                 positions=None, rope_theta: float = 10000.0,
                 use_rope: bool = True, cap=None, q_chunk=512, kv_chunk=512,
                 banded_causal: bool = False):
    """Training/encoding forward, no cache. x: (b, s, d)."""
    q, k, v = _qkv(params, x, n_kv)
    if use_rope:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                                         x.shape[:2])
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    o = chunked_attention(q, k, v, causal=causal, window=window, cap=cap,
                          q_chunk=q_chunk, kv_chunk=kv_chunk,
                          banded_causal=banded_causal)
    return _out(params, o)


def attn_prefill(params, x, cache, *, n_kv: int, window: int = 0,
                 rope_theta: float = 10000.0, use_rope: bool = True,
                 cap=None, q_chunk=512, kv_chunk=512):
    q, k, v = _qkv(params, x, n_kv)
    if use_rope:
        positions = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                                     x.shape[:2])
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    o = chunked_attention(q, k, v, causal=True, window=window, cap=cap,
                          q_chunk=q_chunk, kv_chunk=kv_chunk)
    new_cache = cache_update_prefill(cache, k, v)
    return _out(params, o), new_cache


def attn_decode(params, x, cache, *, n_kv: int, window: int = 0,
                rope_theta: float = 10000.0, use_rope: bool = True, cap=None):
    """x: (b, 1, d) single new token."""
    q, k, v = _qkv(params, x, n_kv)
    if use_rope:
        pos = jnp.broadcast_to(cache["pos"][None, None], (x.shape[0], 1))
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    cache = cache_update_decode(cache, k, v)
    o = decode_attend(q, cache, window=window, cap=cap)
    return _out(params, o), cache


# ---------------------------------------------------------------------------
# Cross attention (enc-dec)
# ---------------------------------------------------------------------------

def cross_kv(params, enc_out):
    xc = enc_out.astype(COMPUTE_DTYPE)
    k = jnp.einsum("bsd,dhk->bshk", xc, params["wk"].astype(COMPUTE_DTYPE))
    v = jnp.einsum("bsd,dhk->bshk", xc, params["wv"].astype(COMPUTE_DTYPE))
    return shard_act(k, "batch", "kv_seq", "kv_heads", None), \
        shard_act(v, "batch", "kv_seq", "kv_heads", None)


def cross_attend(params, x, k, v, *, q_chunk=512, kv_chunk=512, cap=None):
    xc = x.astype(COMPUTE_DTYPE)
    q = jnp.einsum("bsd,dhk->bshk", xc, params["wq"].astype(COMPUTE_DTYPE))
    o = chunked_attention(q, k, v, causal=False, cap=cap,
                          q_chunk=q_chunk, kv_chunk=kv_chunk)
    return _out(params, o)
