"""Mamba2-780M: weights from the seed, the program's layout, and the
plain reference the served tokens are checked against.

The reference follows Mamba-2 (Dao & Gu, arXiv:2405.21060) in its
recurrent form, in float32: in_proj -> [z, xBC, dt]; depthwise causal
conv (width 4, bias) over xBC with SiLU; per head
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = C_t h_t + D x_t;
gated RMSNorm(y * silu(z)); out_proj; pre-norm residual blocks and tied
embeddings. Both projections are served SiTe CiM layers
(``cim.cim_dense``). It runs the same batched calls the engine ran:
left-padded fills against fresh state, where pad columns feed zeros to
the conv and carry dt = 0, and single-token steps over every slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import cim

PROGRAM_KEYS = {
    "d_model": "d_model",
    "n_layer": "n_layers",
    "vocab_size": "vocab",
    "d_state": "ssm_state",
    "d_conv": "ssm_conv_width",
    "expand": "ssm_expand",
    "headdim": "ssm_head_dim",
    "ngroups": "ssm_n_groups",
    "chunk_size": "ssm_chunk",
}
# ids of the published tokenizer; the program's table has padding rows
VOCAB_IDS = 50277


def dims(c):
    d = c["d_model"]
    di = c["expand"] * d
    n, g = c["d_state"], c["ngroups"]
    h = di // c["headdim"]
    return dict(d=d, di=di, n=n, g=g, h=h, p=c["headdim"], w=c["d_conv"],
                L=c["n_layer"], V=c["vocab_size"], conv=di + 2 * g * n,
                proj=2 * di + 2 * g * n + h)


def make_weights(c, key):
    """Every weight from ``key`` on the device in one jitted call, in
    the type the program serves it in (bfloat16; A_log, D and dt_bias
    float32)."""
    z = dims(c)

    @jax.jit
    def build(key):
        ks = iter(jax.random.split(key, 16))
        L, d, di, h = z["L"], z["d"], z["di"], z["h"]
        nrm = lambda shape: jax.random.normal(next(ks), shape, jnp.float32)
        bf = lambda a: a.astype(jnp.bfloat16)
        # dt initialised as in mamba_ssm: log-uniform in [1e-3, 1e-1],
        # stored through the inverse softplus
        dt = jnp.exp(jax.random.uniform(next(ks), (L, h), minval=jnp.log(1e-3),
                                        maxval=jnp.log(1e-1)))
        return {
            "embed": bf(0.02 * nrm((z["V"], d))),
            "final_norm": bf(1.0 + 0.1 * nrm((d,))),
            "ln1": bf(1.0 + 0.1 * nrm((L, d))),
            "w_in": bf(nrm((L, d, z["proj"])) * d ** -0.5),
            "conv_w": bf(nrm((L, z["w"], z["conv"])) * z["w"] ** -0.5),
            "conv_b": bf(0.1 * nrm((L, z["conv"]))),
            "A_log": jnp.log(jax.random.uniform(next(ks), (L, h), minval=1.0,
                                                maxval=16.0)),
            "D": 1.0 + 0.1 * nrm((L, h)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": bf(1.0 + 0.1 * nrm((L, di))),
            "w_out": bf(nrm((L, di, d)) * di ** -0.5),
        }

    return build(key)


def to_program(w):
    """The program's parameter tree; its unembedding leaf is the
    embedding's transpose (tied, as published)."""
    m = {k: w[k] for k in ("w_in", "conv_w", "conv_b", "A_log", "D",
                           "dt_bias", "norm", "w_out")}
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "unembed": w["embed"].T,
            "blocks": {"ln1": w["ln1"], "mamba": m}}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


class Reference:
    """Plain float32 recurrence over ``n`` rows; ``dtype`` other than
    None rounds every activation and weight to that type (the control).
    The SSM state is float32 as served."""

    def __init__(self, c, w, n, s_max, dtype=None):
        self.z = dims(c)
        if self.z["g"] != 1:
            raise ValueError("this reference implements ngroups = 1")
        self.eps = float(c["norm_epsilon"])
        self.r = r = cim.Rounding(dtype)
        self.n = n
        f32 = lambda a: r(a.astype(jnp.float32))
        layers = {k: f32(w[k]) for k in ("ln1", "conv_w", "conv_b", "norm")}
        layers.update({k: w[k].astype(jnp.float32) for k in ("A_log", "D", "dt_bias")})
        for k in ("w_in", "w_out"):
            layers[k + "_t"], layers[k + "_s"] = cim.ternary_weight(w[k])
        self.params = (layers, f32(w["embed"]), f32(w["final_norm"]))
        self.cache = self._zeros()

    def _zeros(self):
        z = self.z
        return (jnp.zeros((z["L"], self.n, z["w"] - 1, z["conv"]), jnp.float32),
                jnp.zeros((z["L"], self.n, z["h"], z["p"], z["n"]), jnp.float32))

    def _forward(self, params, tokens, conv, state, valid):
        z, r = self.z, self.r
        layers, embed, final_norm = params
        b, s = tokens.shape
        di, n, h, p = z["di"], z["n"], z["h"], z["p"]
        x = jnp.take(embed, tokens, axis=0)

        def layer(x, lw):
            q, conv, st = lw
            hid = r(_rms(x, q["ln1"], self.eps))
            zx = r(cim.cim_dense(hid, q["w_in_t"], q["w_in_s"]))
            gate, xbc = zx[..., :di], zx[..., di:di + di + 2 * n]
            dt = jax.nn.softplus(zx[..., -h:] + q["dt_bias"])
            xbc = jnp.where(valid[..., None], xbc, 0.0)
            dt = jnp.where(valid[..., None], dt, 0.0)
            win = jnp.concatenate([conv, xbc], axis=1)
            xc = sum(win[:, i:i + s] * q["conv_w"][i] for i in range(z["w"]))
            xc = r(jax.nn.silu(xc + q["conv_b"]))
            xs = xc[..., :di].reshape(b, s, h, p)
            bm, cm = xc[..., di:di + n], xc[..., di + n:]
            decay = jnp.exp(dt * -jnp.exp(q["A_log"]))

            def tick(st, t):
                xt, bt, ct, dtt, at = t
                st = st * at[:, :, None, None] + (
                    dtt[:, :, None, None] * xt[..., None] * bt[:, None, None, :])
                return st, jnp.einsum("bhpn,bn->bhp", st, ct)

            seq = lambda v: jnp.moveaxis(v, 1, 0)
            st, ys = jax.lax.scan(tick, st, (seq(xs), seq(bm), seq(cm), seq(dt),
                                             seq(decay)))
            y = r((jnp.moveaxis(ys, 0, 1) + xs * q["D"][:, None]).reshape(b, s, di))
            y = r(_rms(r(y * jax.nn.silu(gate)), q["norm"], self.eps))
            x = r(x + r(cim.cim_dense(y, q["w_out_t"], q["w_out_s"])))
            return x, (win[:, s:], st)

        x, (conv, state) = jax.lax.scan(layer, x, (layers, conv, state))
        x = r(_rms(x[:, -1], final_norm, self.eps))
        return jnp.einsum("bd,vd->bv", x, embed), conv, state

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(2,))
    def _prefill(self, params, cache, tokens, start, fill):
        with jax.default_matmul_precision("highest"):
            valid = jnp.arange(tokens.shape[1])[None] >= start[:, None]
            logits, conv, st = self._forward(params, tokens, *self._zeros(), valid)
        sel = lambda new, old: jnp.where(
            fill.reshape((1, -1) + (1,) * (old.ndim - 2)), new, old)
        return logits, (sel(conv, cache[0]), sel(st, cache[1]))

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(2,))
    def _decode(self, params, cache, tokens, positions, start):
        with jax.default_matmul_precision("highest"):
            valid = (positions >= start)[:, None]
            logits, conv, st = self._forward(params, tokens[:, None], *cache, valid)
        return logits, (conv, st)

    def prefill(self, tokens, start, fill):
        logits, self.cache = self._prefill(self.params, self.cache, tokens,
                                           start, fill)
        return logits

    def decode(self, tokens, positions, start):
        logits, self.cache = self._decode(self.params, self.cache, tokens,
                                          positions, start)
        return logits
