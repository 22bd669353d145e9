"""Mamba2 — SSD (state-space duality) layer, chunked scan + O(1) decode.

Implements the minimal SSD form of Mamba-2 (Dao & Gu, arXiv:2405.21060):

  h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t x_t^T        (per head)
  y_t = C_t h_t + D x_t

computed with the chunked algorithm: within-chunk quadratic attention-like
term + inter-chunk state recurrence (a lax.scan over chunks, O(L) total).
Decode keeps (conv_state, ssm_state) caches for O(1) per-token steps —
this is why mamba2/zamba2 are the archs assigned the ``long_500k`` cell.

Projections route through ``layers.dense`` (ternary/CiM modes apply); the
state recurrence itself is activation math and stays bf16 (DESIGN.md §5).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L


class SSMCache(NamedTuple):
    conv: jax.Array   # (B, W-1, conv_channels) rolling conv window
    state: jax.Array  # (B, H, P, N) ssm state

    @staticmethod
    def zeros(batch: int, cfg: ArchConfig, dtype=jnp.float32):
        di = cfg.ssm_d_inner
        conv_ch = di + 2 * cfg.ssm_n_groups * cfg.ssm_state
        h = cfg.ssm_n_heads
        p = cfg.ssm_head_dim
        return SSMCache(
            jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_ch), dtype),
            jnp.zeros((batch, h, p, cfg.ssm_state), dtype),
        )


def init_mamba2(key, cfg: ArchConfig, dtype=jnp.float32):
    d = cfg.d_model
    di = cfg.ssm_d_inner
    g, n = cfg.ssm_n_groups, cfg.ssm_state
    h = cfg.ssm_n_heads
    conv_ch = di + 2 * g * n
    ks = jax.random.split(key, 5)
    return {
        # in_proj -> [z (di), x (di), B (g*n), C (g*n), dt (h)]
        "w_in": L.init_dense_weight(ks[0], (d, 2 * di + 2 * g * n + h), dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_ch)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "w_out": L.init_dense_weight(ks[4], (di, d), dtype=dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, S, C), w: (W, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + xp[:, i : i + x.shape[1], :] * w[i][None, None, :]
    return out + b[None, None, :]


def _ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """Chunked SSD. Shapes:
      x: (b, l, h, p), dt: (b, l, h), A: (h,) negative decay rates,
      B, C: (b, l, g, n). Returns y: (b, l, h, p), final_state (b, h, p, n).
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    heads_per_group = h // g

    # broadcast B, C to heads
    Bh = jnp.repeat(B, heads_per_group, axis=2)  # (b, l, h, n)
    Ch = jnp.repeat(C, heads_per_group, axis=2)

    # reshape to chunks
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = Bh.reshape(b, nc, chunk, h, n)
    Cc = Ch.reshape(b, nc, chunk, h, n)

    dA = dtc * A[None, None, None, :]                # (b, nc, c, h) negative
    cum = jnp.cumsum(dA, axis=2)                     # within-chunk cumulative

    # --- within-chunk (quadratic in chunk) ---
    # L[i, j] = exp(cum_i - cum_j) for j <= i. Mask the *argument* before
    # exp: masked (j > i) entries have positive arguments whose exp
    # overflows, and where(mask, inf, 0) produces NaN gradients.
    li = cum[:, :, :, None, :]                       # (b, nc, c, 1, h)
    lj = cum[:, :, None, :, :]                       # (b, nc, 1, c, h)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    delta = jnp.where(mask[None, None, :, :, None], li - lj, -1e30)
    decay = jnp.exp(delta)
    cb = jnp.einsum("bzihn,bzjhn->bzijh", Cc, Bc)    # (b, nc, c, c, h)
    att = cb * decay
    y_diag = jnp.einsum("bzijh,bzjh,bzjhp->bzihp", att, dtc, xc)

    # --- chunk states ---
    chunk_sum = cum[:, :, -1, :]                     # (b, nc, h) total decay
    # state contribution of each position: decay to end of chunk
    state_w = jnp.exp(chunk_sum[:, :, None, :] - cum)  # (b, nc, c, h)
    states = jnp.einsum("bzch,bzch,bzchn,bzchp->bzhpn", state_w, dtc, Bc, xc)

    # --- inter-chunk recurrence (scan over chunks) ---
    def step(h_prev, inp):
        st, dsum = inp                               # (b,h,p,n), (b,h)
        h_new = h_prev * jnp.exp(dsum)[:, :, None, None] + st
        return h_new, h_prev

    h0 = jnp.zeros((b, h, p, n), x.dtype)
    states_t = jnp.moveaxis(states, 1, 0)            # (nc, b, h, p, n)
    dsum_t = jnp.moveaxis(chunk_sum, 1, 0)           # (nc, b, h)
    h_final, h_prevs = jax.lax.scan(step, h0, (states_t, dsum_t))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)            # (b, nc, h, p, n) state entering chunk

    # --- contribution of carried-in state to each position ---
    pos_decay = jnp.exp(cum)                         # (b, nc, c, h)
    y_carry = jnp.einsum("bzchn,bzhpn,bzch->bzchp", Cc, h_prevs, pos_decay)

    y = (y_diag + y_carry).reshape(b, l, h, p)
    y = y + x * D[None, None, :, None]
    return y, h_final


@L.scoped("ssm")
def mamba2_block(
    params,
    x: jax.Array,
    cfg: ArchConfig,
    cache: Optional[SSMCache] = None,
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[SSMCache]]:
    """x: (B, S, D). Without cache: chunked parallel form (training /
    prefill). With cache: any S ≥ 1 — S = 1 is the O(1) decode step,
    S > 1 is cached prefill (conv window seeded from the cache, state
    recurrence continued from ``cache.state``).

    ``valid`` (B, S) marks real columns in a left-padded batched prefill:
    pad columns contribute nothing — their raw conv inputs are zeroed
    (matching the zero-initialized conv window of an unpadded run) and
    their dt is zeroed, which freezes the state (exp(0·A) = 1, no B·x
    injection)."""
    b, s, d = x.shape
    di = cfg.ssm_d_inner
    g, n = cfg.ssm_n_groups, cfg.ssm_state
    h, p = cfg.ssm_n_heads, cfg.ssm_head_dim
    qc = cfg.quant

    zxbcdt = L.dense(x, params["w_in"], qc)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : di + di + 2 * g * n]
    dt_raw = zxbcdt[..., -h:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"])

    if cache is None:
        xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
        xbc = jax.nn.silu(xbc)
        xs = xbc[..., :di].reshape(b, s, h, p).astype(jnp.float32)
        B_ = xbc[..., di : di + g * n].reshape(b, s, g, n).astype(jnp.float32)
        C_ = xbc[..., di + g * n :].reshape(b, s, g, n).astype(jnp.float32)
        chunk = min(cfg.ssm_chunk, s)
        pad = (-s) % chunk
        if pad:
            xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
            B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
            C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        y, h_final = _ssd_chunked(xs, dt, A, B_, C_, params["D"], chunk)
        y = y[:, :s]
        new_cache = None
    else:
        # decode / cached prefill: the last W-1 *raw* conv inputs ride in
        # cache.conv; run the depthwise causal conv over the extended
        # window and continue the state recurrence from cache.state with
        # a sequential scan over the S new tokens (S = 1: one recurrent
        # update, the O(1) decode step).
        if valid is not None:
            keep = valid[:, :, None]
            xbc = jnp.where(keep, xbc, jnp.zeros((), xbc.dtype))
            dt = jnp.where(valid[:, :, None], dt, 0.0)
        conv_in = jnp.concatenate([cache.conv, xbc], axis=1)  # (B, W-1+S, C)
        w = params["conv_w"]
        width = w.shape[0]
        conv_out = sum(
            conv_in[:, i : i + s, :] * w[i][None, None, :] for i in range(width)
        )
        xbc_f = jax.nn.silu(conv_out + params["conv_b"][None, None, :])
        xs = xbc_f[..., :di].reshape(b, s, h, p).astype(jnp.float32)
        B_ = xbc_f[..., di : di + g * n].reshape(b, s, g, n).astype(jnp.float32)
        C_ = xbc_f[..., di + g * n :].reshape(b, s, g, n).astype(jnp.float32)
        hp = h // g
        Bh = jnp.repeat(B_, hp, axis=2)                       # (b, s, h, n)
        Ch = jnp.repeat(C_, hp, axis=2)
        dA = jnp.exp(dt * A[None, None, :])                   # (b, s, h)

        def step(state, inp):
            x_t, B_t, C_t, dt_t, dA_t = inp
            state = state * dA_t[:, :, None, None] + jnp.einsum(
                "bh,bhn,bhp->bhpn", dt_t, B_t, x_t)
            y_t = jnp.einsum("bhn,bhpn->bhp", C_t, state)
            return state, y_t

        to_time = lambda a: jnp.moveaxis(a, 1, 0)
        state, ys = jax.lax.scan(
            step, cache.state,
            (to_time(xs), to_time(Bh), to_time(Ch), to_time(dt), to_time(dA)),
        )
        y = jnp.moveaxis(ys, 0, 1)                            # (b, s, h, p)
        y = y + xs * params["D"][None, None, :, None]
        new_cache = SSMCache(conv=conv_in[:, s:], state=state)

    y = y.reshape(b, s, di).astype(x.dtype)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), params["norm"])
    return L.dense(y, params["w_out"], qc, tp="row"), new_cache
