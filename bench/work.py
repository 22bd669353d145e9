"""Operations and least bytes of the served work, from shapes alone.

A CiM call multiplies (M, K) activations by (K, N) ternary weights:
2*M*K*N operations over the logical M, K and N (never the padded
tiles). Its least traffic is the weights at 2 bits an element (the
paper's stored cell), the activations at 2 bits where the configuration
ternarizes them and at 2 bytes where it serves them in bfloat16, and the
output at 2 bytes an element. Time is judged against the int8 peak where
both operands are ternary (the product fits int8 exactly) and against
the bfloat16 peak where the activations are bfloat16. So a kernel that
serves packed 2-bit planes reads as a higher share, never above 100%.

Model operations per token are 2 x the parameters of every matrix that
runs through the dense layers and the unembedding; attention and SSM
state arithmetic are left out, so MFU is a lower bound.
"""
from __future__ import annotations


def matrices(c: dict):
    """[(name, K, N)] of one layer's CiM projections, and the
    unembedding's (K, N), for a configuration file's dict."""
    if "hidden_size" in c:  # llama
        d, f = c["hidden_size"], c["intermediate_size"]
        q = c["num_attention_heads"] * c["head_dim"]
        kv = c["num_key_value_heads"] * c["head_dim"]
        layer = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
                 ("w_gate", d, f), ("w_up", d, f), ("w_down", f, d)]
        return layer, c["num_hidden_layers"], (d, c["vocab_size"])
    d = c["d_model"]  # mamba2
    di = c["expand"] * d
    g, n = c["ngroups"], c["d_state"]
    proj = 2 * di + 2 * g * n + di // c["headdim"]
    return ([("w_in", d, proj), ("w_out", di, d)], c["n_layer"],
            (d, c["vocab_size"]))


def ternary_activations(c: dict) -> bool:
    """Whether the configuration ternarizes what enters the array."""
    return c["served"]["quant"]["quantize_activations"]


def ops_peak(c: dict, peak: dict) -> float:
    """The chip's peak for the configuration's CiM products."""
    return peak["int8_ops"] if ternary_activations(c) else peak["bf16_flops"]


def cim_call(c: dict, m: int, k: int, n: int):
    """(operations, least bytes) of one CiM MAC."""
    x_bytes = m * k * 2 // 8 if ternary_activations(c) else m * k * 2
    return 2 * m * k * n, x_bytes + k * n * 2 // 8 + m * n * 2


def least_time(ops: float, nbytes: float, ops_per_s: float, peak: dict):
    """(seconds, bound) of work at ``ops_per_s`` and the HBM peak."""
    t_ops = ops / ops_per_s
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def cim_step(c: dict, m: int):
    """(operations, least bytes) of every CiM call of one model step on
    ``m`` rows."""
    layer, n_layers, _ = matrices(c)
    ops = nbytes = 0
    for _, k, n in layer:
        o, b = cim_call(c, m, k, n)
        ops += o * n_layers
        nbytes += b * n_layers
    return ops, nbytes


def ops_per_token(c: dict) -> int:
    """Model operations of one token: 2 x dense and unembedding params."""
    layer, n_layers, (k, n) = matrices(c)
    return 2 * (n_layers * sum(kk * nn for _, kk, nn in layer) + k * n)
