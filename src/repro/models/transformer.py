"""Model assembly: decoder LMs (dense / MoE / MLA), SSM, hybrid, enc-dec,
and VLM — all from one functional toolkit, scan-over-layers, cache-aware.

Entry points:
  * init_params(key, cfg)              — parameter pytree (stacked layers)
  * forward(params, batch, cfg)        — training/teacher-forced logits
  * init_caches(cfg, batch, s_max)     — decode caches
  * decode_step(params, tokens, caches, index, cfg) — one-token step
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import shard_act
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

PyTree = Any


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def init_block(key, cfg: ArchConfig, dtype):
    """One decoder layer's params, by family."""
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm" or (cfg.family == "hybrid"):
        return {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "mamba": ssm_lib.init_mamba2(ks[0], cfg, dtype),
        }
    p = {"ln1": jnp.ones((cfg.d_model,), dtype), "ln2": jnp.ones((cfg.d_model,), dtype)}
    if cfg.mla:
        p["attn"] = attn.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = attn.init_gqa(ks[0], cfg, dtype)
    if cfg.n_experts:
        p["moe"] = moe_lib.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    if cfg.family == "encdec":
        p["ln_x"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = attn.init_cross(ks[2], cfg, dtype)
    return p


def apply_block(
    p: PyTree,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array,
    cache: Optional[PyTree],
    cache_index,
    enc: Optional[jax.Array] = None,
    start: Optional[jax.Array] = None,
):
    """Returns (x, new_cache). ``cache_index`` is scalar or (B,) (ragged
    decode); ``start`` is the (B,) left-padding dead-zone boundary —
    attention masks cache slots below it, SSM blocks zero the padded
    columns' state/conv contributions (pad columns have positions < 0)."""
    if "mamba" in p:
        h = L.rms_norm(x, p["ln1"])
        valid = None
        if cache is not None and start is not None:
            valid = positions >= 0  # (B, S): left-pad columns are inert
        out, new_cache = ssm_lib.mamba2_block(p["mamba"], h, cfg, cache, valid=valid)
        return x + out, new_cache

    h = L.rms_norm(x, p["ln1"])
    if cfg.mla:
        a, new_cache = attn.mla_attention(
            p["attn"], h, cfg, positions, cache, cache_index, start)
    else:
        a, new_cache = attn.gqa_attention(
            p["attn"], h, cfg, positions, cache, cache_index, start)
    x = x + a
    x = shard_act(x, "btd")
    if enc is not None and "cross" in p:
        h = L.rms_norm(x, p["ln_x"])
        x = x + attn.cross_attention(p["cross"], h, enc, cfg)
    h = L.rms_norm(x, p["ln2"])
    if "moe" in p:
        x = x + moe_lib.moe_block(p["moe"], h, cfg)
    else:
        x = x + L.mlp(p["mlp"], h, cfg.quant)
    return shard_act(x, "btd"), new_cache


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig) -> PyTree:
    dtype = _dtype(cfg)
    k_embed, k_blocks, k_head, k_extra = jax.random.split(key, 4)
    params: Dict[str, PyTree] = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, cfg.d_model)) * 0.02).astype(dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_dense_weight(k_head, (cfg.d_model, cfg.vocab), dtype=dtype)

    if cfg.scan_layers:
        keys = jax.random.split(k_blocks, cfg.n_layers)
        params["blocks"] = jax.vmap(lambda k: init_block(k, cfg, dtype))(keys)
    else:
        keys = jax.random.split(k_blocks, cfg.n_layers)
        params["blocks"] = [init_block(k, cfg, dtype) for k in keys]

    if cfg.family == "hybrid":
        ke1, ke2 = jax.random.split(k_extra)
        params["shared_attn"] = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": attn.init_gqa(ke1, cfg, dtype),
            "mlp": L.init_mlp(ke2, cfg.d_model, cfg.d_ff, dtype),
        }
    if cfg.family == "encdec":
        ke = jax.random.split(k_extra, cfg.n_encoder_layers + 1)
        enc_cfg = cfg  # same width
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_encoder_block(k, enc_cfg, dtype)
        )(ke[: cfg.n_encoder_layers])
        params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
        params["enc_pos"] = (
            jax.random.normal(ke[-1], (cfg.encoder_seq, cfg.d_model)) * 0.02
        ).astype(dtype)
    if cfg.family == "vlm":
        params["projector"] = L.init_dense_weight(k_extra, (cfg.d_vision, cfg.d_model), dtype=dtype)
    return params


def _init_encoder_block(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attn.init_cross(k1, cfg, dtype),  # self-attn uses same shape set
        "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _encoder_block_apply(p, x, cfg):
    h = L.rms_norm(x, p["ln1"])
    x = x + attn.cross_attention(p["attn"], h, h, cfg)  # self-attention (no mask)
    h = L.rms_norm(x, p["ln2"])
    return x + L.mlp(p["mlp"], h, cfg.quant)


def run_encoder(params, frames: jax.Array, cfg: ArchConfig) -> jax.Array:
    """frames: (B, S_enc, D) precomputed frame embeddings (conv stub)."""
    x = frames + params["enc_pos"][None, : frames.shape[1], :].astype(frames.dtype)

    def body(carry, p):
        return _encoder_block_apply(p, carry, cfg), None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return L.rms_norm(x, params["enc_norm"])


# ---------------------------------------------------------------------------
# Layer-stack execution (scan, remat, hybrid segments)
# ---------------------------------------------------------------------------

def _scan_stack(blocks, x, cfg, positions, caches, cache_index, enc=None, start=None):
    """Scan over stacked layer params; caches may be None."""
    if isinstance(blocks, list):  # scan_layers=False: unrolled python loop
        new_cs = []
        for i, p in enumerate(blocks):
            c = jax.tree.map(lambda a: a[i], caches) if caches is not None else None
            x, nc = apply_block(p, x, cfg, positions, c, cache_index, enc, start)
            new_cs.append(nc)
        if caches is None:
            return x, None
        return x, jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_cs)

    def body(carry, xs):
        if caches is None:
            p, c = xs, None
        else:
            p, c = xs
        y, new_c = apply_block(p, carry, cfg, positions, c, cache_index, enc, start)
        return y, (new_c if caches is not None else 0)

    if cfg.remat and caches is None:
        body = jax.checkpoint(body, prevent_cse=False)
    xs = blocks if caches is None else (blocks, caches)
    x, outs = jax.lax.scan(body, x, xs)
    new_caches = outs if caches is not None else None
    return x, new_caches


def _run_hybrid(params, x, cfg, positions, caches, cache_index, start=None):
    """zamba2: mamba backbone with a weight-shared attention block applied
    every ``hybrid_attn_every`` layers. caches = (ssm_caches_stacked,
    attn_caches_stacked_per_application) or None."""
    k = cfg.hybrid_attn_every
    n_seg = cfg.n_layers // k
    sp = params["shared_attn"]

    ssm_caches, attn_caches = caches if caches is not None else (None, None)
    new_ssm, new_attn = [], []
    for s in range(n_seg):
        seg_blocks = jax.tree.map(lambda a: a[s * k : (s + 1) * k], params["blocks"])
        seg_cache = (
            jax.tree.map(lambda a: a[s * k : (s + 1) * k], ssm_caches)
            if ssm_caches is not None
            else None
        )
        x, nc = _scan_stack(seg_blocks, x, cfg, positions, seg_cache, cache_index,
                            start=start)
        if nc is not None:
            new_ssm.append(nc)
        # shared attention block (weights reused; per-application KV cache)
        h = L.rms_norm(x, sp["ln1"])
        # cache class rides the pytree (KVCache or QuantKVCache — §13)
        ac = type(attn_caches)(*(a[s] for a in attn_caches)) if attn_caches is not None else None
        a, nac = attn.gqa_attention(sp["attn"], h, cfg, positions, ac, cache_index, start)
        x = x + a
        h = L.rms_norm(x, sp["ln2"])
        x = x + L.mlp(sp["mlp"], h, cfg.quant)
        if nac is not None:
            # write just the new-token slice into this application's cache
            attn_caches = type(attn_caches)(
                *(
                    _write_token_slice(stack, n, s, cache_index)
                    for stack, n in zip(attn_caches, tuple(nac))
                )
            )
    if caches is None:
        return x, None
    new_caches = (
        jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *new_ssm),
        attn_caches,
    )
    return x, new_caches


# ---------------------------------------------------------------------------
# Forward (training / teacher-forced)
# ---------------------------------------------------------------------------

def embed_inputs(params, batch: Dict[str, jax.Array], cfg: ArchConfig) -> jax.Array:
    x = L.embed(batch["tokens"], params["embed"])
    if cfg.family == "vlm":
        patches = batch["patches"].astype(x.dtype)
        img = L.dense(patches, params["projector"], cfg.quant)
        x = jnp.concatenate([img, x], axis=1)
    return x


def forward(params, batch: Dict[str, jax.Array], cfg: ArchConfig) -> jax.Array:
    """Teacher-forced logits: (B, S_total, V)."""
    x = embed_inputs(params, batch, cfg).astype(_dtype(cfg))
    x = shard_act(x, "btd")
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    enc = None
    if cfg.family == "encdec":
        enc = run_encoder(params, batch["frames"].astype(x.dtype), cfg)
    if cfg.family == "hybrid":
        x, _ = _run_hybrid(params, x, cfg, positions, None, None)
    else:
        x, _ = _scan_stack(params["blocks"], x, cfg, positions, None, None, enc)
    x = L.rms_norm(x, params["final_norm"])
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    qc = cfg.quant if cfg.quantize_unembed else L.QuantConfig(mode="off")
    logits = L.dense(x, table, qc)
    return shard_act(logits, "logits")


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------

def _gqa_cache_zeros(cfg: ArchConfig, batch: int, s_max: int, dtype):
    """One layer's GQA cache honoring ``cfg.quant.cache_dtype``
    (DESIGN.md §13): bf16 keeps the exact pre-§13 buffers; int8/ternary
    build quantized codes + per-(row, position) scale leaves."""
    cd = cfg.quant.cache_dtype
    if cd == "bf16":
        return attn.KVCache.zeros(
            batch, s_max, cfg.n_kv_heads, cfg.resolved_head_dim, dtype)
    return attn.QuantKVCache.zeros(
        batch, s_max, cfg.n_kv_heads, cfg.resolved_head_dim, cd)


def init_caches(cfg: ArchConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    """Stacked decode caches for the whole layer stack. Attention caches
    follow ``cfg.quant.cache_dtype``; SSM conv/state caches stay exact
    f32 (they are small, fully rewritten each step, and carry recurrent
    state whose quantization error would compound)."""
    if cfg.family == "ssm":
        one = ssm_lib.SSMCache.zeros(batch, cfg, jnp.float32)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape), one
        )
    if cfg.family == "hybrid":
        ssm_one = ssm_lib.SSMCache.zeros(batch, cfg, jnp.float32)
        ssm_stack = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape), ssm_one
        )
        n_seg = cfg.n_layers // cfg.hybrid_attn_every
        kv_one = _gqa_cache_zeros(cfg, batch, s_max, dtype)
        kv_stack = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_seg,) + a.shape), kv_one
        )
        return (ssm_stack, kv_stack)
    if cfg.mla:
        cd = cfg.quant.cache_dtype
        if cd == "bf16":
            one = attn.MLACache.zeros(
                batch, s_max, cfg.kv_lora_rank, cfg.qk_rope_head_dim, dtype)
        else:
            one = attn.QuantMLACache.zeros(
                batch, s_max, cfg.kv_lora_rank, cfg.qk_rope_head_dim, cd)
    else:
        one = _gqa_cache_zeros(cfg, batch, s_max, dtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape), one
    )


def _wrap_cache(cfg: ArchConfig, tree):
    if cfg.family in ("ssm",):
        return ssm_lib.SSMCache(*tree)
    quant = cfg.quant.cache_dtype != "bf16"
    if cfg.mla:
        return (attn.QuantMLACache if quant else attn.MLACache)(*tree)
    return (attn.QuantKVCache if quant else attn.KVCache)(*tree)


def _write_token_slice(stack: jax.Array, sl: jax.Array, layer, index) -> jax.Array:
    """Write a new-token cache slice (B, s, ...) into a stacked cache
    (L, B, S_max, ...) at (layer, :, index). Only the token slice moves —
    the decode-traffic discipline (DESIGN.md §Perf). ``index`` may be a
    (B,) vector (ragged decode): each batch row then lands at its own
    sequence offset via a vmapped per-row update."""
    sl = sl.astype(stack.dtype)
    if jnp.ndim(index) == 0:
        starts = (layer, 0, index) + (0,) * (stack.ndim - 3)
        return jax.lax.dynamic_update_slice(stack, sl[None], starts)
    return stack.at[layer].set(attn.write_cache_rows(stack[layer], sl, index))


def _write_full_state(stack: jax.Array, st: jax.Array, layer) -> jax.Array:
    """Replace a whole per-layer state (SSM: the state is small and fully
    rewritten every step by construction)."""
    starts = (layer,) + (0,) * (stack.ndim - 1)
    return jax.lax.dynamic_update_slice(stack, st[None].astype(stack.dtype), starts)


def decode_step(
    params,
    tokens: jax.Array,
    caches,
    index: jax.Array,
    cfg: ArchConfig,
    enc: Optional[jax.Array] = None,
    start: Optional[jax.Array] = None,
) -> Tuple[jax.Array, PyTree]:
    """One decode step. tokens: (B, S_step) (S_step=1 for pure decode);
    ``index`` is the write offset into the caches — a scalar (every row
    at the same position: prefill / ``generate()``) or a (B,) vector
    (ragged decode: continuous-batching slots at heterogeneous
    positions). Returns (logits, caches).

    ``start`` (optional, (B,)) is the left-padding dead-zone boundary of
    a batched ragged prefill: cache slots below ``start[i]`` hold pad
    garbage and stay masked; RoPE positions are computed in *logical*
    coordinates ``index - start`` so each row's first real token is
    position 0 regardless of padding (DESIGN.md §6).

    The layer scan reads each layer's cache as an ``xs`` slice and emits
    only what is new: the token's K/V slices (attention; written into
    the stacked caches after the scan, in place) or the layer's new
    state (SSM; the scan's outputs are the new caches).
    """
    x = L.embed(tokens, params["embed"]).astype(_dtype(cfg))
    b, s = x.shape[:2]
    idx = jnp.asarray(index, jnp.int32)
    base = idx if start is None else idx - start  # logical position of token 0
    positions = (
        jnp.broadcast_to(base, (b,))[:, None]
        + jnp.arange(s, dtype=jnp.int32)[None, :]
    )
    if cfg.family == "hybrid":
        x, new_caches = _run_hybrid(params, x, cfg, positions, caches, idx, start)
    else:
        stacks = tuple(caches)
        ssm_like = cfg.family == "ssm"

        # Scan reads each layer's cache as an xs slice (no carry mutation)
        # and emits only the new-token slice / new state as ys. Ragged
        # decode attends through a select view of the slice (the new token
        # at its row's own position) that fuses into the contractions, so
        # nothing of cache size is written inside the scan; after it,
        # _write_kv puts each slot's token slice into the donated caches
        # in place. Decode HBM traffic is one cache read plus the tokens.
        def body(y, xs):
            p, c = xs
            c = _wrap_cache(cfg, c)
            y, new_c = apply_block(p, y, cfg, positions, c, idx, enc, start)
            return y, tuple(new_c)

        x, token_slices = jax.lax.scan(body, x, (params["blocks"], stacks))
        if ssm_like:
            new_caches = _wrap_cache(cfg, token_slices)
        else:
            new_caches = _wrap_cache(cfg, _write_kv(stacks, token_slices, idx))
    x = L.rms_norm(x, params["final_norm"])
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    with jax.named_scope("unembed"):
        logits = L.dense(x, table, L.QuantConfig(mode="off"))
    return logits, new_caches


@L.scoped("kv.write")
def _write_kv(stacks, token_slices, idx):
    """Write every layer's new-token KV slices (L, B, s, ...) into the
    stacked caches after the layer scan: at one sequence offset
    (scalar ``idx``), or each row at its own (ragged decode).

    The ragged write is a loop over slots, each an in-place
    dynamic_update_slice of that slot's (L, 1, s, ...) slice at
    ``(0, b, idx[b])``: the donated caches keep their own layout. (A
    vmapped or ``.at[]`` write lowers to a scatter, for which the TPU
    compiler relays out both whole caches and back every step.)"""
    if idx.ndim == 0:
        return tuple(
            jax.lax.dynamic_update_slice(
                stack,
                ts.astype(stack.dtype),
                (0, 0, idx) + (0,) * (stack.ndim - 3),
            )
            for stack, ts in zip(stacks, token_slices)
        )

    def slot(b, stacks):
        return tuple(
            jax.lax.dynamic_update_slice(
                stack,
                jax.lax.dynamic_slice_in_dim(ts, b, 1, axis=1).astype(stack.dtype),
                (0, b, idx[b]) + (0,) * (stack.ndim - 3),
            )
            for stack, ts in zip(stacks, token_slices)
        )

    return jax.lax.fori_loop(0, idx.shape[0], slot, tuple(stacks))
