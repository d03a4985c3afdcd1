"""Operations and bytes that the served programs' work needs, counted from
the configuration and the shapes each call ran at (not from what the
implementation happens to compute: masked attention blocks, padded decode
views and rewritten caches are not work).

A layer's matrix multiplications: Q, K, V and output projections and the
three SwiGLU matrices.  Attention: QK^T and PV, 2 operations per multiply-
add, over the causal pairs only.  The head runs once per prefill (its last
position) and once per decoded token.  Bytes are those read and written by
a call at the served dtype: the weights it uses, the KV it reads and the KV
and activations it writes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    dtype_bytes: int = 2

    @classmethod
    def of(cls, c: dict) -> "Dims":
        return cls(c["num_hidden_layers"], c["hidden_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"], c["intermediate_size"], c["vocab_size"],
                   {"bfloat16": 2, "float32": 4}[c["torch_dtype"]])


def layer_matmul_params(m: Dims) -> int:
    q = m.d * m.heads * m.head_dim
    kv = 2 * m.d * m.kv_heads * m.head_dim
    o = m.heads * m.head_dim * m.d
    return q + kv + o + 3 * m.d * m.ff


def layer_weight_bytes(m: Dims) -> int:
    return (layer_matmul_params(m) + 2 * m.d) * m.dtype_bytes


def head_weight_bytes(m: Dims) -> int:
    return (m.d * m.vocab + m.d) * m.dtype_bytes


def kv_bytes_per_token_layer(m: Dims) -> int:
    return 2 * m.kv_heads * m.head_dim * m.dtype_bytes


def causal_pairs(S: int, prefix: int = 0) -> int:
    """Query-key pairs of S new queries after `prefix` cached positions."""
    return S * prefix + S * (S + 1) // 2


def prefill_flops(m: Dims, S: int, n_layers: int, prefix: int = 0) -> float:
    mm = 2.0 * S * layer_matmul_params(m)
    attn = 4.0 * m.heads * m.head_dim * causal_pairs(S, prefix)
    return n_layers * (mm + attn)


def prefill_bytes(m: Dims, S: int, n_layers: int, prefix: int = 0) -> float:
    act = 2.0 * S * m.d * m.dtype_bytes                      # x in, x out
    kv = (S + prefix) * kv_bytes_per_token_layer(m) + \
        S * kv_bytes_per_token_layer(m)                     # read + write
    return n_layers * (layer_weight_bytes(m) + kv) + act


def head_flops(m: Dims, rows: int = 1) -> float:
    return 2.0 * rows * m.d * m.vocab


def head_bytes(m: Dims, rows: int = 1) -> float:
    return head_weight_bytes(m) + rows * (m.d + m.vocab) * m.dtype_bytes


def decode_flops(m: Dims, lens: Sequence[int]) -> float:
    """One step for active slots whose caches hold `lens` tokens."""
    per = 2.0 * layer_matmul_params(m) * m.layers + head_flops(m)
    attn = 4.0 * m.heads * m.head_dim * m.layers * sum(n + 1 for n in lens)
    return per * len(lens) + attn


def decode_bytes(m: Dims, lens: Sequence[int]) -> float:
    kv = kv_bytes_per_token_layer(m) * m.layers
    return (layer_weight_bytes(m) * m.layers + head_weight_bytes(m)
            + kv * sum(n + 1 for n in lens))


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, 'compute' or 'memory'): the larger bound wins."""
    tc, tm = flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
