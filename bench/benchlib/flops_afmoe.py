"""Operations of the afmoe (Trinity) served work, counted from
the configuration file the cell runs (`bench/configs/<name>.json`) and the
shapes each call ran at, as `flops.py` counts the dense model's.

A layer's matrix multiplications: Q, K, V, the gate and the output
projection; then on the dense lead the SwiGLU of `intermediate_size`, on
the MoE layers the router over all `router_experts`, the shared expert,
and the held experts a token reaches: `num_experts_per_tok` x held /
router_experts of them on average (the routed experts outside this chip's
share are not this chip's work).  Attention: QK^T and PV over the causal
pairs, on a windowed layer only those at most `sliding_window - 1` apart.
A prefill quantum of n layers counts n times the mean layer (its taps do
not say which layers ran; a prompt's quanta together run every layer).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Optional, Sequence, Tuple

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int                   # dense lead
    f: int                    # routed expert
    fs: int                   # shared expert
    experts: int              # router outputs
    held: int
    top_k: int
    vocab: int
    window: int
    windowed: Tuple[bool, ...]

    @classmethod
    def of(cls, c: dict) -> "Dims":
        L = c["num_hidden_layers"]
        return cls(L, c["num_dense_layers"], c["hidden_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"], c["intermediate_size"],
                   c["moe_intermediate_size"],
                   c["moe_intermediate_size"] * c["num_shared_experts"],
                   c["router_experts"], c["num_experts"],
                   c["num_experts_per_tok"], c["vocab_size"],
                   c["sliding_window"],
                   tuple(t == "sliding_attention"
                         for t in c["layer_types"][:L]))

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def reach(self) -> float:
        """Held experts a token reaches, on average."""
        return self.top_k * self.held / self.experts


def of_ctx(ctx) -> Optional[Dims]:
    """The dims of the cell's configuration, if it is an afmoe one."""
    cfg = getattr(getattr(ctx, "backend", None), "cfg", None)
    path = CONFIGS / f"{getattr(cfg, 'name', '')}.json"
    if not path.is_file():
        return None
    c = json.loads(path.read_text())
    return Dims.of(c) if c.get("reference") == "afmoe" else None


def attn_params(m: Dims) -> int:
    hd = m.head_dim
    return m.d * hd * (2 * m.heads + 2 * m.kv_heads) + m.heads * hd * m.d


def expert_params(m: Dims) -> int:
    return 3 * m.d * m.f


def mean_layer_params(m: Dims, reached: float) -> float:
    """Matmul parameters a token runs through in the mean layer, with
    `reached` held experts per MoE layer."""
    moe = m.d * m.experts + 3 * m.d * m.fs + reached * expert_params(m)
    return attn_params(m) + (m.dense_layers * 3 * m.d * m.ff
                             + m.moe_layers * moe) / m.layers


def pairs(m: Dims, S: int, prefix: int, window: int) -> int:
    """Query-key pairs of S new queries after `prefix` positions, at most
    `window` - 1 apart (0: causal only)."""
    if not window:
        return S * prefix + S * (S + 1) // 2
    a, b = prefix + 1, prefix + S          # keys each query sees, uncapped
    top = min(b, window)
    below = (top * (top + 1) - (a - 1) * a) // 2 if top >= a else 0
    return below + window * max(0, b - max(a - 1, window))


def mean_layer_pairs(m: Dims, S: int, prefix: int) -> float:
    nw = sum(m.windowed)
    return (nw * pairs(m, S, prefix, m.window)
            + (m.layers - nw) * pairs(m, S, prefix, 0)) / m.layers


def prefill_flops(m: Dims, S: int, n: int, prefix: int = 0) -> float:
    """A quantum of n mean layers over a prompt of S tokens, of which
    `prefix` were reused."""
    new = S - prefix
    mm = 2.0 * new * mean_layer_params(m, m.reach)
    return n * (mm + 4.0 * m.heads * m.head_dim
                * mean_layer_pairs(m, new, prefix))


def head_flops(m: Dims, rows: int = 1) -> float:
    return 2.0 * rows * m.d * m.vocab


def decode_kv_tokens(m: Dims, lens: Sequence[int]) -> float:
    """KV positions one step reads over all layers."""
    nw = sum(m.windowed)
    return sum((m.layers - nw) * (n + 1) + nw * min(n + 1, m.window)
               for n in lens)


def decode_flops(m: Dims, lens: Sequence[int]) -> float:
    per = 2.0 * mean_layer_params(m, m.reach) * m.layers + head_flops(m)
    attn = 4.0 * m.heads * m.head_dim * decode_kv_tokens(m, lens)
    return per * len(lens) + attn
