"""GQA attention: training (blocked causal flash, exact T²/2 flops), prefill,
and single-token decode with KV cache (+ cross-shard split-KV merge).

The training path blocks queries with a static python loop and scans only
the causally-needed KV blocks per query block, so compiled FLOPs match the
T²/2 causal ideal (no masked-out wasted compute) and peak activation memory
is O(B·H·qblock·kvblock) — this is what lets prefill_32k fit per-device.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import common as cm

_NEG = -1e30


def attn_params(key, cfg, d_model=None, dtype=jnp.float32, out_scale=1.0):
    d = d_model or cfg.d_model
    hd, h, hkv = cfg.hd, cfg.n_heads, cfg.n_kv
    ks = jax.random.split(key, 4)
    std = 0.02
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype) * std,
        "wk": jax.random.normal(ks[1], (d, hkv * hd), dtype) * std,
        "wv": jax.random.normal(ks[2], (d, hkv * hd), dtype) * std,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype) * std * out_scale,
    }
    if cfg.bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def qkv(p, cfg, x):
    b, t, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, t, h, hd)
    k = k.reshape(b, t, hkv, hd)
    v = v.reshape(b, t, hkv, hd)
    if cfg.qk_norm:
        q = cm.rms_norm(q, p["q_norm"])
        k = cm.rms_norm(k, p["k_norm"])
    return q, k, v


# ------------------------------------------------- blocked causal attn ----

def _block_attn(q, k, v, *, causal_offset=None):
    """q (B,Hkv,G,Tq,D), k/v (B,Hkv,Tk,D) -> (out, m, l) online-softmax stats.
    causal_offset: (q_start, k_start) for the causal mask, or None (full)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k).astype(jnp.float32) * scale
    if causal_offset is not None:
        q0, k0 = causal_offset
        qi = q0 + jnp.arange(q.shape[3])
        ki = k0 + jnp.arange(k.shape[2])
        s = jnp.where(qi[:, None] >= ki[None, :], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(q.dtype), v)
    return out, m[..., 0], l[..., 0]


def flash_attention(
    q: jax.Array,            # (B, T, H, D)
    k: jax.Array,            # (B, Tk, Hkv, D)
    v: jax.Array,
    causal: bool = True,
    q_block: int = 512,
    kv_block: int = 512,
) -> jax.Array:
    """Memory-efficient exact attention.  Static python loop over query
    blocks; each block scans only its causally-visible KV blocks."""
    b, t, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    q_block = min(q_block, t)
    kv_block = min(kv_block, tk)
    # pad to block multiples (padded queries discarded; padded keys masked
    # by the causal offset / explicit length mask)
    tp = ((t + q_block - 1) // q_block) * q_block
    tkp = ((tk + kv_block - 1) // kv_block) * kv_block
    qp = jnp.pad(q, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, tkp - tk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, tkp - tk), (0, 0), (0, 0)))

    qg = jnp.transpose(qp.reshape(b, tp, hkv, g, d), (0, 2, 3, 1, 4))
    kg = jnp.transpose(kp, (0, 2, 1, 3))             # (B, Hkv, Tk, D)
    vg = jnp.transpose(vp, (0, 2, 1, 3))

    nq = tp // q_block
    outs = []
    for qi in range(nq):
        q0 = qi * q_block
        qblk = jax.lax.slice_in_dim(qg, q0, q0 + q_block, axis=3)
        # causally visible KV prefix for this query block
        k_hi = min(tkp, ((q0 + q_block + kv_block - 1) // kv_block) * kv_block) \
            if causal else tkp
        nkv = k_hi // kv_block

        def kv_step(carry, idx):
            acc, m_run, l_run = carry
            k0 = idx * kv_block
            kblk = jax.lax.dynamic_slice_in_dim(kg, k0, kv_block, axis=2)
            vblk = jax.lax.dynamic_slice_in_dim(vg, k0, kv_block, axis=2)
            if causal:
                o, m_new, l_new = _block_attn(
                    qblk, kblk, vblk, causal_offset=(q0, k0)
                )
            else:
                # full attention; mask key padding explicitly
                scale = 1.0 / math.sqrt(d)
                s = jnp.einsum(
                    "bhgqd,bhkd->bhgqk", qblk, kblk
                ).astype(jnp.float32) * scale
                valid = (k0 + jnp.arange(kv_block)) < tk
                s = jnp.where(valid[None, None, None, None, :], s, _NEG)
                m_new = jnp.max(s, axis=-1)
                pw = jnp.exp(s - m_new[..., None])
                l_new = jnp.sum(pw, axis=-1)
                o = jnp.einsum("bhgqk,bhkd->bhgqd", pw.astype(qblk.dtype), vblk)
            m_tot = jnp.maximum(m_run, m_new)
            a_old = jnp.exp(m_run - m_tot)
            a_new = jnp.exp(m_new - m_tot)
            acc = acc * a_old[..., None].astype(acc.dtype) \
                + o * a_new[..., None].astype(o.dtype)
            l_run = l_run * a_old + l_new * a_new
            return (acc, m_tot, l_run), None

        acc0 = jnp.zeros((b, hkv, g, q_block, d), q.dtype)
        m0 = jnp.full((b, hkv, g, q_block), _NEG, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_block), jnp.float32)
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), jnp.arange(nkv)
        )
        outs.append(acc / jnp.maximum(l_run, 1e-30)[..., None].astype(acc.dtype))

    out = jnp.concatenate(outs, axis=3)              # (B, Hkv, G, Tp, D)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, tp, h, d)
    return out[:, :t]


def attention_train(p, cfg, x, cos_sin=None, kv_override=None, causal=True):
    """Full attention sub-block: qkv -> rope -> flash -> out proj.
    kv_override: (k, v) from the encoder for cross-attention."""
    b, t, _ = x.shape
    q, k, v = qkv(p, cfg, x)
    if kv_override is not None:
        k, v = kv_override
    if cos_sin is not None:
        cos, sin = cos_sin
        q = cm.apply_rope(q, cos, sin)
        if kv_override is None:
            k = cm.apply_rope(k, cos, sin)
    o = flash_attention(q, k, v, causal=causal)
    return o.reshape(b, t, -1) @ p["wo"].astype(x.dtype)


# -------------------------------------------------------------- decode ----

# Baseline decode upcasts the cache operands to f32 before the einsums
# (explicit f32 math).  The §Perf hillclimb flips this to False: operands
# stay bf16 (MXU-native) with f32 ACCUMULATION via preferred_element_type —
# same numerics class, half the HBM traffic on the O(S) cache reads.
DECODE_UPCAST = True


def decode_attention_jnp(q, k_cache, v_cache, kv_len):
    """One-token GQA decode, pure jnp (GSPMD-shardable baseline).
    q (B, H, D); caches (B, S, Hkv, D); kv_len: valid prefix length."""
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    scale = 1.0 / math.sqrt(d)
    if DECODE_UPCAST:
        s_ = jnp.einsum(
            "bhgd,bshd->bhgs", qg.astype(jnp.float32),
            k_cache.astype(jnp.float32),
        ) * scale
    else:
        s_ = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s) < kv_len
    s_ = jnp.where(mask[None, None, None, :], s_, _NEG)
    w = jax.nn.softmax(s_, axis=-1)
    if DECODE_UPCAST:
        o = jnp.einsum("bhgs,bshd->bhgd", w, v_cache.astype(jnp.float32))
    else:
        o = jnp.einsum("bhgs,bshd->bhgd", w.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
    return o.reshape(b, h, d).astype(q.dtype)


# When set (e.g. "model"), single-token decode attention runs as a MANUAL
# split-KV over that mesh axis: each shard computes partial softmax stats
# over its local slice of the sequence-sharded cache, and shards merge with
# ONE pmax + ONE fused psum of O(H·D) — the paper's fused-single-reduction
# discipline applied to serving (DESIGN.md §8).  None = let GSPMD choose
# (the baseline the §Perf hillclimb measures against).
SPLIT_KV_AXIS: str | None = None


def split_kv_decode(q, k_cache, v_cache, kv_len, axis: str):
    """Explicit split-KV decode: caches sequence-sharded over ``axis``.
    Runs under jit via partial-manual shard_map (manual only on ``axis``)."""
    def local(qf, kf, vf, kvl):
        b, h, d = qf.shape
        s_loc, hkv = kf.shape[1], kf.shape[2]
        g = h // hkv
        qg = qf.reshape(b, hkv, g, d)
        scale = 1.0 / math.sqrt(d)
        if DECODE_UPCAST:
            s_ = jnp.einsum(
                "bhgd,bshd->bhgs", qg.astype(jnp.float32),
                kf.astype(jnp.float32)) * scale
        else:
            s_ = jnp.einsum("bhgd,bshd->bhgs", qg, kf,
                            preferred_element_type=jnp.float32) * scale
        idx0 = jax.lax.axis_index(axis) * s_loc
        mask = (idx0 + jnp.arange(s_loc)) < kvl
        s_ = jnp.where(mask[None, None, None, :], s_, _NEG)
        m = jnp.max(s_, axis=-1, keepdims=True)              # (B,Hkv,G,1)
        p = jnp.exp(s_ - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        if DECODE_UPCAST:
            o = jnp.einsum("bhgs,bshd->bhgd", p, vf.astype(jnp.float32))
        else:
            o = jnp.einsum("bhgs,bshd->bhgd", p.astype(vf.dtype), vf,
                           preferred_element_type=jnp.float32)
        out = merge_decode_shards(o, m, l, axis)             # 1 pmax + 1 psum
        return out.reshape(b, h, d).astype(qf.dtype)

    from jax.sharding import PartitionSpec as P
    in_specs = (P(), P(None, axis, None, None), P(None, axis, None, None),
                P())
    return jax.shard_map(
        local, axis_names={axis}, in_specs=in_specs, out_specs=P(),
    )(q, k_cache, v_cache, kv_len)


def merge_decode_shards(o, m, l, axis):
    """Split-KV cross-shard combine for the Pallas decode kernel
    (DESIGN.md §8): per-shard unnormalized (o, m, l) -> exact softmax
    combine with ONE pmax + ONE fused psum of O(H·D), never O(S)."""
    m_glob = jax.lax.pmax(m, axis)
    scale = jnp.exp(m - m_glob)
    num_den = jax.lax.psum(
        jnp.concatenate([o * scale, l * scale], axis=-1), axis
    )
    o_sum = num_den[..., : o.shape[-1]]
    l_sum = num_den[..., o.shape[-1] :]
    return o_sum / jnp.maximum(l_sum, 1e-30)


def decode_step(p, cfg, x, k_cache, v_cache, pos, cos_sin):
    """Append one token to the cache and attend.  x (B, 1, D); pos scalar.
    Returns (out (B,1,D), k_cache, v_cache)."""
    b = x.shape[0]
    q, k, v = qkv(p, cfg, x)                        # (B,1,H,D)/(B,1,Hkv,D)
    cos, sin = cos_sin
    q = cm.apply_rope(q, cos, sin)
    k = cm.apply_rope(k, cos, sin)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), pos, axis=1)
    if SPLIT_KV_AXIS is not None:
        o = split_kv_decode(q[:, 0], k_cache, v_cache, pos + 1, SPLIT_KV_AXIS)
    else:
        o = decode_attention_jnp(q[:, 0], k_cache, v_cache, pos + 1)
    out = o.reshape(b, 1, -1) @ p["wo"].astype(x.dtype)
    return out, k_cache, v_cache
