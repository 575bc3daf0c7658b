"""Operations and bytes of one decode step, from a configuration's sizes.

Every weight matmul of the dense decoder's decode step is one call of the
protected matmul (``FTContext.matmul`` -> ``ft_matmul`` under the fused
dispatch).  :func:`decode_calls` lists them at the shapes the model hands
the call: ``x (m, k) @ w (k, n)``, ``m`` the decode slots.  Bytes are
counted in bfloat16, the model's compute type, for ``x``, ``w`` and the
output, whatever the implementation moves.  The LM head's ``n`` is the
embedding table's rows, the vocabulary rounded up to 256 as the model
stores it.

Model FLOPs of a step count real tokens only: for each active slot, two
per matmul parameter (the head at the true vocabulary) and four per
(layer, head, head-dim, attended position) for scores and values.
"""
from __future__ import annotations

import dataclasses

BF16_BYTES = 2


@dataclasses.dataclass(frozen=True)
class Call:
    site: str
    m: int
    k: int
    n: int
    count: int          # calls per step

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.k * self.n * self.count

    @property
    def bytes(self) -> float:
        return float(BF16_BYTES * (self.m * self.k + self.k * self.n + self.m * self.n) * self.count)

    def least_s(self, peaks) -> float:
        """The shortest time the chip could take for these calls."""
        return max(self.flops / peaks.bf16_flops, self.bytes / peaks.hbm_bw)


def _sizes(config: dict) -> dict:
    d, h = config["hidden_size"], config["num_attention_heads"]
    hd = d // h
    return {
        "d": d, "h": h, "hk": config["num_key_value_heads"], "hd": hd,
        "ff": config["intermediate_size"], "layers": config["num_hidden_layers"],
        "vocab": config["vocab_size"], "vocab_rows": -(-config["vocab_size"] // 256) * 256,
        "gated": config["hidden_act"] == "silu",
    }


def decode_calls(config: dict, n_slots: int) -> list[Call]:
    """Every protected matmul of one decode step over ``n_slots`` slots."""
    s = _sizes(config)
    m, d, L = n_slots, s["d"], s["layers"]
    q, kv = s["h"] * s["hd"], s["hk"] * s["hd"]
    if kv == q:
        calls = [Call("attn.qkv", m, d, q, 3 * L)]
    else:
        calls = [Call("attn.qkv", m, d, q, L), Call("attn.qkv", m, d, kv, 2 * L)]
    return calls + [
        Call("attn.out", m, q, d, L),
        Call("ffn", m, d, s["ff"], (2 if s["gated"] else 1) * L),
        Call("ffn", m, s["ff"], d, L),
        Call("head", m, d, s["vocab_rows"], 1),
    ]


def matmul_params_per_token(config: dict) -> int:
    """Weights one token multiplies through: every layer's projections and
    the head over the true vocabulary."""
    s = _sizes(config)
    d, q, kv = s["d"], s["h"] * s["hd"], s["hk"] * s["hd"]
    per_layer = d * q + 2 * d * kv + q * d + (3 if s["gated"] else 2) * d * s["ff"]
    return s["layers"] * per_layer + d * s["vocab"]


def attn_flops_per_position(config: dict) -> int:
    """Score and value FLOPs of one token for each position it attends to."""
    s = _sizes(config)
    return 4 * s["layers"] * s["h"] * s["hd"]


def step_model_flops(config: dict, active: int, attended: int) -> float:
    """Model FLOPs of a step in which ``active`` slots each fed one token and
    attended to ``attended`` positions between them."""
    return 2.0 * matmul_params_per_token(config) * active + attn_flops_per_position(config) * attended
