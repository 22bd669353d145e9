"""SmolLM-135M: weights from the seed, the program's layout, and the
plain reference the served tokens are checked against.

The reference follows the published Llama architecture (RMSNorm,
rotate-half RoPE, grouped-query attention, SwiGLU, tied embeddings) in
float32, with every projection replaced by the served SiTe CiM layer of
``cim.cim_dense``. It runs the same batched calls the engine ran (all
slots, left-padded fills against fresh caches, ragged decode
positions); its KV cache is stored in the served cache type (bfloat16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import cim

PROGRAM_KEYS = {  # configuration key -> program ArchConfig attribute
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "resolved_head_dim",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
}


def dims(c):
    return dict(d=c["hidden_size"], f=c["intermediate_size"],
                h=c["num_attention_heads"], kv=c["num_key_value_heads"],
                hd=c["head_dim"], L=c["num_hidden_layers"], V=c["vocab_size"])


def make_weights(c, key):
    """Every weight from ``key`` on the device in one jitted call, in
    the served type (bfloat16)."""
    z = dims(c)

    @jax.jit
    def build(key):
        ks = iter(jax.random.split(key, 16))
        d, f, L = z["d"], z["f"], z["L"]
        qd, kvd = z["h"] * z["hd"], z["kv"] * z["hd"]

        def dense(shape):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * shape[-2] ** -0.5).astype(jnp.bfloat16)

        def gamma(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape)).astype(jnp.bfloat16)

        return {
            "embed": (0.02 * jax.random.normal(next(ks), (z["V"], d))).astype(jnp.bfloat16),
            "final_norm": gamma((d,)),
            "ln1": gamma((L, d)), "ln2": gamma((L, d)),
            "wq": dense((L, d, qd)), "wk": dense((L, d, kvd)),
            "wv": dense((L, d, kvd)), "wo": dense((L, qd, d)),
            "w_gate": dense((L, d, f)), "w_up": dense((L, d, f)),
            "w_down": dense((L, f, d)),
        }

    return build(key)


def to_program(w):
    """The program's parameter tree (stacked layers), sharing arrays."""
    return {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "blocks": {
            "ln1": w["ln1"], "ln2": w["ln2"],
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        },
    }


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate-half RoPE. x (B, S, H, D), pos (B, S)."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


class Reference:
    """Plain float32 forward over ``n`` cache rows of ``s_max`` positions
    (the KV cache stored in the served type, bfloat16). ``dtype`` other
    than None rounds every activation and weight to that type: the
    control."""

    def __init__(self, c, w, n, s_max, dtype=None):
        self.z = dims(c)
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.r = r = cim.Rounding(dtype)
        self.n, self.s_max = n, s_max
        f32 = lambda a: r(a.astype(jnp.float32))
        layers = {k: f32(w[k]) for k in ("ln1", "ln2")}
        for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            layers[k + "_t"], layers[k + "_s"] = cim.ternary_weight(w[k])
        self.params = (layers, f32(w["embed"]), f32(w["final_norm"]))
        self.cache = self._zeros()

    def _zeros(self):
        z = self.z
        shape = (z["L"], self.n, self.s_max, z["kv"], z["hd"])
        return (jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16))

    def _forward(self, params, tokens, kc, vc, index, start):
        """tokens (B, S); index (B,) cache offset of column 0; start (B,)
        first live cache slot. Returns last-column logits and caches."""
        z, r = self.z, self.r
        layers, embed, final_norm = params
        b, s = tokens.shape
        x = jnp.take(embed, tokens, axis=0)
        pos = (index - start)[:, None] + jnp.arange(s)[None]
        qpos = index[:, None] + jnp.arange(s)[None]
        kpos = jnp.arange(self.s_max)
        live = ((kpos[None, None] <= qpos[:, :, None])
                & (kpos[None, None] >= start[:, None, None]))
        g = z["h"] // z["kv"]

        def write(buf, new):
            return jax.vmap(lambda bb, nn, i: jax.lax.dynamic_update_slice(
                bb, nn, (i, 0, 0)))(buf, new.astype(buf.dtype), index)

        def layer(x, lw):
            p, k_buf, v_buf = lw
            dense = lambda t, n: r(cim.cim_dense(t, p[n + "_t"], p[n + "_s"]))
            h = r(_rms(x, p["ln1"], self.eps))
            q = dense(h, "wq").reshape(b, s, z["h"], z["hd"])
            k = dense(h, "wk").reshape(b, s, z["kv"], z["hd"])
            v = dense(h, "wv").reshape(b, s, z["kv"], z["hd"])
            q, k = r(_rope(q, pos, self.theta)), r(_rope(k, pos, self.theta))
            k_buf, v_buf = write(k_buf, k), write(v_buf, v)
            qg = q.reshape(b, s, z["kv"], g, z["hd"])
            sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_buf.astype(jnp.float32))
            sc = sc / jnp.sqrt(jnp.float32(z["hd"]))
            sc = jnp.where(live[:, None, None], sc, -1e30)
            pr = r(jax.nn.softmax(sc, axis=-1))
            o = jnp.einsum("bhgqk,bkhd->bqhgd", pr, v_buf.astype(jnp.float32))
            x = r(x + dense(r(o.reshape(b, s, z["h"] * z["hd"])), "wo"))
            h = r(_rms(x, p["ln2"], self.eps))
            u = r(jax.nn.silu(dense(h, "w_gate")) * dense(h, "w_up"))
            x = r(x + dense(u, "w_down"))
            return x, (k_buf, v_buf)

        x, (kc, vc) = jax.lax.scan(layer, x, (layers, kc, vc))
        x = r(_rms(x[:, -1], final_norm, self.eps))
        return jnp.einsum("bd,vd->bv", x, embed), kc, vc

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(2,))
    def _prefill(self, params, cache, tokens, start, fill):
        with jax.default_matmul_precision("highest"):
            zero = jnp.zeros((tokens.shape[0],), jnp.int32)
            logits, kc, vc = self._forward(params, tokens, *self._zeros(), zero,
                                           start)
        m = fill[None, :, None, None, None]
        return logits, (jnp.where(m, kc, cache[0]), jnp.where(m, vc, cache[1]))

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(2,))
    def _decode(self, params, cache, tokens, positions, start):
        with jax.default_matmul_precision("highest"):
            logits, kc, vc = self._forward(params, tokens[:, None], *cache,
                                           positions, start)
        return logits, (kc, vc)

    def prefill(self, tokens, start, fill):
        logits, self.cache = self._prefill(self.params, self.cache, tokens,
                                           start, fill)
        return logits

    def decode(self, tokens, positions, start):
        logits, self.cache = self._decode(self.params, self.cache, tokens,
                                          positions, start)
        return logits
