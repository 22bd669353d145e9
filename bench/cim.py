"""Plain SiTe CiM arithmetic shared by the configurations' references.

Written from the paper's description and the served configuration's
``quant`` block, in float32: TWN threshold ternarization of each weight
per output channel (delta = 0.7 * mean|w|, scale = mean of |w| above
delta), and the CiM MAC in which every 16-row block of the contraction
yields a = (sum|x||w| + sum x w) / 2 and b = (sum|x||w| - sum x w) / 2
(the signed event counts for ternary x; bitline partial sums for
multi-level x), each clamped at the ADC bound 8 before the blocks are
summed. The served activations enter the array unquantized.

``Rounding`` names the precision that a reference holds its activations
to: float32 for the reference itself, float8 for the control that has
to fail the check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

THRESHOLD_FACTOR = 0.7
BLOCK = 16
ADC_MAX = 8.0
# rows of a CiM MAC evaluated at once: bounds the (rows, K/16, N) block
# intermediates to a few hundred MB
_CHUNK_BYTES = 256 << 20


def ternary_weight(w):
    """Per-output-channel TWN ternarization of a (..., K, N) weight, in
    the weight's served type (its mean and sums accumulate in float32
    and round to that type): codes in {-1, 0, 1} and the (..., 1, N)
    scale, both as float32."""
    absw = jnp.abs(w)
    delta = THRESHOLD_FACTOR * jnp.mean(absw, axis=-2, keepdims=True)
    mask = (absw > delta).astype(w.dtype)
    num = jnp.sum(absw * mask, axis=-2, keepdims=True)
    den = jnp.maximum(jnp.sum(mask, axis=-2, keepdims=True), 1.0)
    scale = (num / den).astype(w.dtype)
    return (jnp.sign(w) * mask).astype(jnp.float32), scale.astype(jnp.float32)


def cim_matmul(x, wt):
    """(M, K) activations x (K, N) ternary codes -> float32 (M, N), with
    the per-block ADC clamp."""
    m, k = x.shape
    n = wt.shape[1]
    pad = (-k) % BLOCK
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        wt = jnp.pad(wt, ((0, pad), (0, 0)))
    kb = (k + pad) // BLOCK
    wb = wt.astype(jnp.float32).reshape(kb, BLOCK, n)
    wa = jnp.abs(wb)

    def rows(xr):
        xb = xr.astype(jnp.float32).reshape(xr.shape[0], kb, BLOCK)
        p = jnp.einsum("mki,kin->mkn", xb, wb)
        mm = jnp.einsum("mki,kin->mkn", jnp.abs(xb), wa)
        a = (mm + p) * 0.5
        b = (mm - p) * 0.5
        return jnp.sum(jnp.minimum(a, ADC_MAX) - jnp.minimum(b, ADC_MAX), axis=1)

    chunk = max(8, _CHUNK_BYTES // (kb * n * 8))
    if m <= chunk:
        return rows(x)
    chunk = 1 << (chunk.bit_length() - 1)
    mp = -(-m // chunk) * chunk
    xc = jnp.pad(x, ((0, mp - m), (0, 0))).reshape(mp // chunk, chunk, -1)
    return jax.lax.map(rows, xc).reshape(mp, n)[:m]


def cim_dense(x, wt, sw):
    """One served CiM layer: x (..., K) through the array holding codes
    ``wt`` (K, N); the per-channel weight scale ``sw`` (1, N) folds into
    the output."""
    out = cim_matmul(x.reshape(-1, x.shape[-1]), wt)
    return out.reshape(x.shape[:-1] + (wt.shape[1],)) * sw[0]


class Rounding:
    """Rounds a reference's floating-point values to ``dtype`` (None
    keeps float32)."""

    def __init__(self, dtype=None):
        self.dtype = None if dtype is None else jnp.dtype(dtype)

    def __call__(self, x):
        if self.dtype is None:
            return x
        return x.astype(self.dtype).astype(jnp.float32)
