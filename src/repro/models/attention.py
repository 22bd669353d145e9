"""Attention: GQA (llama-family) and MLA (deepseek-v2), with KV caches.

Weight-bearing projections route through ``layers.dense`` so the paper's
ternary/CiM modes apply; the score/value contractions are
activation-activation products and stay bf16 in every mode (CiM is a
weight-stationary paradigm — DESIGN.md §5).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import ternary as tern
from repro.models import layers as L


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, H_kv, Dh)
    v: jax.Array  # (B, S_max, H_kv, Dh)

    @staticmethod
    def zeros(batch: int, s_max: int, n_kv: int, head_dim: int, dtype=jnp.bfloat16):
        return KVCache(
            jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
            jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
        )


class MLACache(NamedTuple):
    """Compressed MLA cache: latent kv (B, S, kv_lora) + rope key (B, S, Dr)."""
    ckv: jax.Array
    k_rope: jax.Array

    @staticmethod
    def zeros(batch: int, s_max: int, kv_lora: int, rope_dim: int, dtype=jnp.bfloat16):
        return MLACache(
            jnp.zeros((batch, s_max, kv_lora), dtype),
            jnp.zeros((batch, s_max, rope_dim), dtype),
        )


# ---------------------------------------------------------------------------
# Quantized KV caches (DESIGN.md §13)
#
# Storage: int8 symmetric codes, or ternary {-1,0,1} codes nibble-packed
# two per byte (uint8). One f32 scale per (row, position) — the same
# granularity as act_scale="per_row": each slot row quantizes
# independently, so continuous batching never couples co-resident
# requests through a shared amax. Dequantization is fused into the
# attention contractions: the codes enter the score/value einsums
# directly and the scale multiplies the (B, ..., Sk) score/prob
# matrices, so no full-precision copy of the stacked cache is ever
# materialized (pinned by the serve.fused_decode_step.kvq contract).
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array, cache_dtype: str) -> Tuple[jax.Array, jax.Array]:
    """Quantize ``x`` (B, S, ...) per (row, position) over every trailing
    axis. Returns ``(codes, scale)`` with scale (B, S) f32:

      * ``"int8"``:    symmetric ``round(x/scale)`` in [-127, 127],
                       ``scale = amax/127`` (1.0 where the slice is all
                       zero — dead pad rows stay exactly zero);
      * ``"ternary"``: TWN codes in {-1,0,1} (:func:`~repro.core.
                       ternary.ternarize`) nibble-packed two per byte
                       along the last axis (uint8, last dim halved).
    """
    red = tuple(range(2, x.ndim))
    xf = x.astype(jnp.float32)
    if cache_dtype == "int8":
        amax = jnp.max(jnp.abs(xf), axis=red)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.round(xf / scale[(...,) + (None,) * len(red)])
        codes = jnp.clip(q, -127, 127).astype(jnp.int8)
        return codes, scale
    if cache_dtype == "ternary":
        t, scale = tern.ternarize(xf, axis=red)
        return pack_ternary_kv(t.astype(jnp.int8)), scale.reshape(x.shape[:2])
    raise ValueError(f"quantize_kv: unknown cache_dtype {cache_dtype!r}")


def pack_ternary_kv(t: jax.Array) -> jax.Array:
    """Pack ternary codes {-1,0,1} (int8) two per byte along the last
    axis: stored nibbles are ``t+1`` in {0,1,2}. Requires an even last
    dim (checked at cache construction)."""
    c = (t + 1).astype(jnp.uint8)
    return (c[..., 0::2] << 4) | c[..., 1::2]


def unpack_ternary_kv(p: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`pack_ternary_kv`: uint8 (..., D/2) -> codes
    (..., D) in {-1,0,1} as ``dtype`` (the attention compute dtype —
    codes are exactly representable in bf16)."""
    hi = ((p >> 4) & 0xF).astype(jnp.int8) - 1
    lo = (p & 0xF).astype(jnp.int8) - 1
    codes = jnp.stack([hi, lo], axis=-1).reshape(p.shape[:-1] + (2 * p.shape[-1],))
    return codes.astype(dtype)


def _kv_codes(buf: jax.Array, dtype) -> jax.Array:
    """Stored cache codes -> compute-dtype codes (int8 pass-through cast,
    uint8 nibble-unpack). The only dequant step besides the score-matrix
    scale multiply — it never touches f32 at cache shape."""
    if buf.dtype == jnp.uint8:
        return unpack_ternary_kv(buf, dtype)
    return buf.astype(dtype)


def _quant_zeros(shape: Tuple[int, ...], cache_dtype: str) -> jax.Array:
    if cache_dtype == "ternary":
        if shape[-1] % 2:
            raise ValueError(
                f"ternary cache_dtype packs 2 codes/byte along the last "
                f"axis; got odd trailing dim {shape[-1]} (shape {shape})"
            )
        # all-zero codes pack to nibble value 1 on both halves
        return jnp.full(shape[:-1] + (shape[-1] // 2,), 0x11, jnp.uint8)
    if cache_dtype == "int8":
        return jnp.zeros(shape, jnp.int8)
    raise ValueError(f"unknown quantized cache_dtype {cache_dtype!r}")


class QuantKVCache(NamedTuple):
    """Quantized GQA cache: codes + per-(row, position) f32 scales.

    ``k``/``v`` are int8 (B, S_max, H_kv, Dh) or ternary-packed uint8
    (B, S_max, H_kv, Dh/2); the storage mode is carried by the leaf
    dtype, so the pytree needs no static flag and generic cache
    plumbing (stacking, donation, sharding) treats every leaf
    uniformly."""
    k: jax.Array
    v: jax.Array
    k_scale: jax.Array  # (B, S_max) f32
    v_scale: jax.Array  # (B, S_max) f32

    @staticmethod
    def zeros(batch: int, s_max: int, n_kv: int, head_dim: int,
              cache_dtype: str = "int8"):
        val = _quant_zeros((batch, s_max, n_kv, head_dim), cache_dtype)
        sc = jnp.ones((batch, s_max), jnp.float32)
        return QuantKVCache(val, val, sc, sc)


class QuantMLACache(NamedTuple):
    """Quantized MLA cache: latent + rope-key codes with per-(row,
    position) scales (storage mode via leaf dtype, as QuantKVCache)."""
    ckv: jax.Array
    k_rope: jax.Array
    ckv_scale: jax.Array    # (B, S_max) f32
    krope_scale: jax.Array  # (B, S_max) f32

    @staticmethod
    def zeros(batch: int, s_max: int, kv_lora: int, rope_dim: int,
              cache_dtype: str = "int8"):
        sc = jnp.ones((batch, s_max), jnp.float32)
        return QuantMLACache(
            _quant_zeros((batch, s_max, kv_lora), cache_dtype),
            _quant_zeros((batch, s_max, rope_dim), cache_dtype),
            sc, sc,
        )


# ---------------------------------------------------------------------------
# Ragged cache writes
# ---------------------------------------------------------------------------

def write_cache_rows(buf: jax.Array, new: jax.Array, index: jax.Array) -> jax.Array:
    """Write ``new`` (B, s, ...) into ``buf`` (B, S_max, ...) at sequence
    offset ``index``.

    ``index`` is the ragged-decode contract's pivot (DESIGN.md §6): a
    scalar means every row writes at the same offset (prefill /
    ``generate()``) and lowers to one contiguous dynamic_update_slice; a
    ``(B,)`` vector means each row lands at its own offset (continuous
    batching over slots at heterogeneous progress) and lowers to a
    vmapped per-row dynamic_update_slice (a batched scatter — rows not
    addressed by their own offset are untouched).
    """
    new = new.astype(buf.dtype)
    if jnp.ndim(index) == 0:
        starts = (0, index) + (0,) * (buf.ndim - 2)
        return jax.lax.dynamic_update_slice(buf, new, starts)

    def row(buf_row, new_row, i):
        starts = (i,) + (0,) * (buf_row.ndim - 1)
        return jax.lax.dynamic_update_slice(buf_row, new_row, starts)

    return jax.vmap(row)(buf, new, index)


def attend_rows(buf: jax.Array, new: jax.Array, index: jax.Array) -> jax.Array:
    """The cache as attention reads it once ``new`` (B, s, ...) sits at
    ``index`` in ``buf`` (B, S_max, ...); the caller writes the cache.

    For one ragged decode token (``index`` of shape (B,), s == 1) this is
    a select, ``new`` where a position is its row's own index and
    ``buf`` elsewhere: it fuses into the score and value contractions,
    which then read the cache once where it lies, and nothing of cache
    size is written. Otherwise it is :func:`write_cache_rows`."""
    if jnp.ndim(index) == 0 or new.shape[1] != 1:
        return write_cache_rows(buf, new, index)
    hit = jnp.arange(buf.shape[1], dtype=jnp.int32)[None, :] == index[:, None]
    hit = hit.reshape(hit.shape + (1,) * (buf.ndim - 2))
    return jnp.where(hit, new.astype(buf.dtype), buf)


def _index_vector(index, b: int) -> jax.Array:
    """Normalize a scalar-or-(B,) cache index to a (B,) int32 vector."""
    return jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ArchConfig, dtype=jnp.float32):
    d, h, hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.init_dense_weight(ks[0], (d, h * hd), dtype=dtype),
        "wk": L.init_dense_weight(ks[1], (d, hkv * hd), dtype=dtype),
        "wv": L.init_dense_weight(ks[2], (d, hkv * hd), dtype=dtype),
        "wo": L.init_dense_weight(ks[3], (h * hd, d), dtype=dtype),
    }


def _sdpa(
    q,
    k,
    v,
    causal_offset,
    length: Optional[jax.Array] = None,
    start: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
):
    """q: (B, Sq, H, Dh); k, v: (B, Sk, Hkv, Dh). GQA via head grouping.

    causal_offset: position of q[0] relative to k[0] (None = no mask).
      Scalar, or (B,) for ragged decode where each row sits at its own
      cache position.
    length: (B,) valid KV length for decode (mask out at and beyond).
    start: (B,) first valid KV slot (mask out below) — left-padded
      batched prefill leaves dead pad slots at the front of each row's
      cache region; they stay masked for the slot's lifetime.
    k_scale/v_scale: (B, Sk) f32 per-(row, position) scales of a
      quantized cache (DESIGN.md §13) — then k/v carry int8 or
      ternary-packed uint8 codes. Dequantization stays fused: codes
      enter the contractions and the scale multiplies the score/prob
      matrices (constant per k-position, so it factors out of the Dh
      contraction); no full-precision cache copy is materialized.
    """
    if k_scale is not None:
        k = _kv_codes(k, q.dtype)
        v = _kv_codes(v, q.dtype)
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    # Context parallelism: shard QUERY rows over the model axis. Head
    # counts rarely divide a 16-way axis (starcoder2: 36 heads), in which
    # case the partitioner replicates the whole score computation; query
    # rows always divide for the training/prefill shapes and each row's
    # softmax is independent. (No-op when activation sharding is off or
    # sq doesn't divide.)
    from repro.dist.sharding import model_axis_size, shard_act

    msize = model_axis_size()
    if msize > 1 and sq % msize == 0 and sq > msize:
        qg = shard_act(qg, "bqhgd_sp")
    # bf16 operands, f32 accumulation (MXU-native; avoids materializing an
    # f32 copy of the KV cache) — see layers.accum_einsum
    scores = L.accum_einsum("bqhgd,bkhd->bhgqk", qg, k.astype(qg.dtype))
    if k_scale is not None:
        scores = scores * k_scale[:, None, None, None, :]
    scores = scores / jnp.sqrt(dh).astype(jnp.float32)
    if causal_offset is not None:
        off = jnp.asarray(causal_offset, jnp.int32)
        off = off[None] if off.ndim == 0 else off        # (1,) or (B,)
        qpos = off[:, None, None] + jnp.arange(sq, dtype=jnp.int32)[None, :, None]
        kpos = jnp.arange(sk, dtype=jnp.int32)[None, None, :]
        mask = kpos <= qpos                              # (1|B, sq, sk)
        scores = jnp.where(mask[:, None, None], scores, -1e30)
    if length is not None:
        valid = jnp.arange(sk)[None, :] < length[:, None]
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    if start is not None:
        live = jnp.arange(sk)[None, :] >= start[:, None]
        scores = jnp.where(live[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale[:, None, None, None, :]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, dh)


def _sdpa_chunked(q, k, v, chunk: int,
                  k_scale: Optional[jax.Array] = None,
                  v_scale: Optional[jax.Array] = None):
    """Flash-style causal attention: scan over KV chunks with an online
    softmax — never materializes the (B, H, Sq, Sk) score matrix. Used for
    long training/prefill sequences (cfg.attn_chunk); numerics match
    :func:`_sdpa` to fp tolerance (tests/test_models.py).

    Optional k_scale/v_scale (B, Sk): quantized-cache codes in k/v, same
    fused-dequant contract as :func:`_sdpa`, applied per KV chunk inside
    the scan (the online softmax never sees a dequantized cache copy)."""
    if k_scale is not None:
        k = _kv_codes(k, q.dtype)
        v = _kv_codes(v, q.dtype)
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    assert sk % chunk == 0, (sk, chunk)
    nc = sk // chunk
    qg = q.reshape(b, sq, hkv, g, dh)
    kc = k.reshape(b, nc, chunk, hkv, dh)
    vc = v.reshape(b, nc, chunk, hkv, dh)
    qpos = jnp.arange(sq)
    scaled = k_scale is not None

    def body(carry, blk):
        m_prev, l_prev, acc = carry
        if scaled:
            kb, vb, ci, ksb, vsb = blk
        else:
            kb, vb, ci = blk                   # (b, chunk, hkv, dh), idx
        s = L.accum_einsum("bqhgd,bkhd->bhgqk", qg, kb.astype(qg.dtype))
        if scaled:
            s = s * ksb[:, None, None, None, :]
        s = s / jnp.sqrt(dh).astype(jnp.float32)
        kpos = ci * chunk + jnp.arange(chunk)
        mask = kpos[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        if scaled:
            p = p * vsb[:, None, None, None, :]
        acc = acc * alpha[..., None] + L.accum_einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb)
        return (m_new, l_new, acc), None

    xs = (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nc))
    if scaled:
        xs = xs + (jnp.moveaxis(k_scale.reshape(b, nc, chunk), 1, 0),
                   jnp.moveaxis(v_scale.reshape(b, nc, chunk), 1, 0))
    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dh), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs)
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return jnp.moveaxis(out, -2, 1).reshape(b, sq, h, dh).astype(q.dtype)


@L.scoped("attn")
def gqa_attention(
    params,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array,
    cache: Optional[KVCache] = None,
    cache_index: Optional[jax.Array] = None,
    start: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """x: (B, S, D). With a cache: decode/prefill-append mode — attention
    runs against the whole cache with the new KV at ``cache_index``
    (scalar, or (B,) for ragged decode where every row sits at its own
    position; :func:`attend_rows`), and the new KV is returned for the
    caller to write. ``start`` marks each row's first valid cache slot
    (left-padding dead zone — see DESIGN.md §6). A :class:`QuantKVCache`
    quantizes the new tokens on write and attends over codes + scales
    (DESIGN.md §13); the :class:`KVCache` path is untouched — bf16
    serving stays bit-identical."""
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    qc = cfg.quant
    q = L.dense(x, params["wq"], qc).reshape(b, s, h, hd)
    k = L.dense(x, params["wk"], qc).reshape(b, s, hkv, hd)
    v = L.dense(x, params["wv"], qc).reshape(b, s, hkv, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        if cfg.attn_chunk and s % cfg.attn_chunk == 0 and s > cfg.attn_chunk:
            out = _sdpa_chunked(q, k, v, cfg.attn_chunk)
        else:
            out = _sdpa(q, k, v, causal_offset=0)
        new_cache = None
    elif isinstance(cache, QuantKVCache):
        cd = "ternary" if cache.k.dtype == jnp.uint8 else "int8"
        k_q, k_s = quantize_kv(k, cd)
        v_q, v_s = quantize_kv(v, cd)
        k_all = attend_rows(cache.k, k_q, cache_index)
        v_all = attend_rows(cache.v, v_q, cache_index)
        ks_all = attend_rows(cache.k_scale, k_s, cache_index)
        vs_all = attend_rows(cache.v_scale, v_s, cache_index)
        new_cache = QuantKVCache(k_q, v_q, k_s, v_s)
        length = _index_vector(cache_index, b) + s
        out = _sdpa(
            q, k_all, v_all, causal_offset=cache_index, length=length,
            start=start, k_scale=ks_all, v_scale=vs_all,
        )
    else:
        k_all = attend_rows(cache.k, k, cache_index)
        v_all = attend_rows(cache.v, v, cache_index)
        # Return only the new-token KV: the caller owns the stacked cache
        # and writes just this slice (avoids restacking the full per-layer
        # cache through the layer scan — decode HBM traffic stays
        # O(read cache + write one token), see DESIGN.md).
        new_cache = KVCache(k.astype(cache.k.dtype), v.astype(cache.v.dtype))
        length = _index_vector(cache_index, b) + s
        out = _sdpa(
            q, k_all, v_all, causal_offset=cache_index, length=length, start=start
        )
    out = out.reshape(b, s, h * hd)
    return L.dense(out, params["wo"], qc, tp="row"), new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v2): low-rank joint KV compression + decoupled rope key
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig, dtype=jnp.float32):
    d, h = cfg.d_model, cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    p = {
        # queries (full rank — q_lora omitted when q_lora_rank == 0)
        "wq": L.init_dense_weight(ks[0], (d, h * (dn + dr)), dtype=dtype),
        # joint KV down-projection + decoupled rope key
        "w_dkv": L.init_dense_weight(ks[1], (d, r + dr), dtype=dtype),
        # up-projections from the latent
        "w_uk": L.init_dense_weight(ks[2], (r, h * dn), dtype=dtype),
        "w_uv": L.init_dense_weight(ks[3], (r, h * dv), dtype=dtype),
        "wo": L.init_dense_weight(ks[4], (h * dv, d), dtype=dtype),
        "kv_norm": jnp.ones((r,), dtype),
    }
    return p


@L.scoped("attn")
def mla_attention(
    params,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array,
    cache: Optional[MLACache] = None,
    cache_index: Optional[jax.Array] = None,
    start: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[MLACache]]:
    b, s, d = x.shape
    h = cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qc = cfg.quant

    q = L.dense(x, params["wq"], qc).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = L.dense(x, params["w_dkv"], qc)
    ckv, k_rope = dkv[..., :r], dkv[..., r:]
    ckv = L.rms_norm(ckv, params["kv_norm"])
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    ckv_scale = krope_scale = None
    if cache is not None and isinstance(cache, QuantMLACache):
        cd = "ternary" if cache.ckv.dtype == jnp.uint8 else "int8"
        ckv_q, ckv_s = quantize_kv(ckv, cd)
        kr_q, kr_s = quantize_kv(k_rope, cd)
        ckv_all = attend_rows(cache.ckv, ckv_q, cache_index)
        krope_all = attend_rows(cache.k_rope, kr_q, cache_index)
        ckv_scale = attend_rows(cache.ckv_scale, ckv_s, cache_index)
        krope_scale = attend_rows(cache.krope_scale, kr_s, cache_index)
        new_cache = QuantMLACache(ckv_q, kr_q, ckv_s, kr_s)
        offset = cache_index
        sk = ckv_all.shape[1]
        length = _index_vector(cache_index, b) + s
    elif cache is not None:
        ckv_all = attend_rows(cache.ckv, ckv, cache_index)
        krope_all = attend_rows(cache.k_rope, k_rope, cache_index)
        # new-token slices only; caller writes them into the stacked cache
        new_cache = MLACache(ckv.astype(cache.ckv.dtype), k_rope.astype(cache.k_rope.dtype))
        offset = cache_index
        sk = ckv_all.shape[1]
        length = _index_vector(cache_index, b) + s
    else:
        ckv_all, krope_all, new_cache, offset, sk, length = ckv, k_rope, None, 0, s, None
        start = None

    # Absorbed-weight form: score = q_nope^T W_uk ckv + q_rope^T k_rope.
    # (decode-efficient: cache stays compressed; W_uk is absorbed into q.)
    # bf16 operands + f32 accumulation: no f32 copy of the latent cache.
    w_uk = params["w_uk"].reshape(r, h, dn).astype(x.dtype)
    q_lat = L.accum_einsum("bqhd,rhd->bqhr", q_nope, w_uk)
    if ckv_scale is not None:
        # quantized latent cache: codes into the contractions, per-(row,
        # position) scales onto the (B, H, Sq, Sk) score parts — the two
        # score terms carry independent scales, so they are applied
        # before the sum (DESIGN.md §13)
        ckv_f = _kv_codes(ckv_all, x.dtype)
        krope_f = _kv_codes(krope_all, q_rope.dtype)
        scores = (L.accum_einsum("bqhr,bkr->bhqk", q_lat.astype(x.dtype), ckv_f)
                  * ckv_scale[:, None, None, :])
        scores = scores + (
            L.accum_einsum("bqhd,bkd->bhqk", q_rope, krope_f)
            * krope_scale[:, None, None, :])
    else:
        scores = L.accum_einsum("bqhr,bkr->bhqk", q_lat.astype(x.dtype),
                                ckv_all.astype(x.dtype))
        scores = scores + L.accum_einsum(
            "bqhd,bkd->bhqk", q_rope, krope_all.astype(q_rope.dtype))
    scores = scores / jnp.sqrt(dn + dr).astype(jnp.float32)
    off = jnp.asarray(offset, jnp.int32)
    off = off[None] if off.ndim == 0 else off            # (1,) or (B,)
    qpos = off[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    kpos = jnp.arange(sk, dtype=jnp.int32)[None, None, :]
    scores = jnp.where((kpos <= qpos[:, :, None])[:, None], scores, -1e30)
    if length is not None:
        valid = jnp.arange(sk)[None, :] < length[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    if start is not None:
        live = jnp.arange(sk)[None, :] >= start[:, None]
        scores = jnp.where(live[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)

    # values from the latent: v = ckv W_uv, attended in latent space first.
    if ckv_scale is not None:
        lat = L.accum_einsum(
            "bhqk,bkr->bqhr",
            (probs * ckv_scale[:, None, None, :]).astype(x.dtype), ckv_f)
    else:
        lat = L.accum_einsum("bhqk,bkr->bqhr", probs.astype(x.dtype),
                             ckv_all.astype(x.dtype))
    w_uv = params["w_uv"].reshape(r, h, dv).astype(x.dtype)
    out = L.accum_einsum("bqhr,rhd->bqhd", lat.astype(x.dtype), w_uv)
    out = out.reshape(b, s, h * dv).astype(x.dtype)
    return L.dense(out, params["wo"], qc, tp="row"), new_cache


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross(key, cfg: ArchConfig, dtype=jnp.float32):
    d, h = cfg.d_model, cfg.n_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.init_dense_weight(ks[0], (d, h * hd), dtype=dtype),
        "wk": L.init_dense_weight(ks[1], (d, h * hd), dtype=dtype),
        "wv": L.init_dense_weight(ks[2], (d, h * hd), dtype=dtype),
        "wo": L.init_dense_weight(ks[3], (h * hd, d), dtype=dtype),
    }


def cross_attention(params, x: jax.Array, enc: jax.Array, cfg: ArchConfig) -> jax.Array:
    b, s, d = x.shape
    se = enc.shape[1]
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    qc = cfg.quant
    q = L.dense(x, params["wq"], qc).reshape(b, s, h, hd)
    k = L.dense(enc, params["wk"], qc).reshape(b, se, h, hd)
    v = L.dense(enc, params["wv"], qc).reshape(b, se, h, hd)
    out = _sdpa(q, k, v, causal_offset=None)
    return L.dense(out.reshape(b, s, h * hd), params["wo"], qc, tp="row")
