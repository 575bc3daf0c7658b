"""Plain float32 reference of the dense decoder family (qwen1.5, starcoder2).

Straight ``jax.numpy`` in float32 with every matmul at ``Precision.HIGHEST``:
no kernels, no KV cache, no batching tricks.  A causal forward over whole
sequences (prompt followed by the served tokens), run layer by layer so that
it fits on one chip after the served program's state is freed.

Layer equations, from the published descriptions:

* embedding lookup; the LM head is the tied embedding table;
* pre-norm residual blocks: ``x += attn(norm1(x))``, ``x += ffn(norm2(x))``;
* norm: RMSNorm (qwen1.5, eps 1e-6) or LayerNorm with bias (starcoder2,
  eps 1e-5);
* attention: q/k/v projections with bias, rotary embedding on the two
  halves of each head (rotate-half form), grouped-query attention (query
  head ``h`` reads key/value head ``h // (n_heads / n_kv)``), causal
  softmax scaled by ``1/sqrt(head_dim)``, output projection;
* FFN: SwiGLU ``down(silu(gate(x)) * up(x))`` or ``down(gelu_tanh(up(x)))``;
* final norm, logits over the true vocabulary only.

Departure, also in the served program: starcoder2 publishes biases on the
attention output and MLP projections (``use_bias``); neither this reference
nor the program carries them (all biases start at zero either way).

Weights come from the seed by the recipe the configurations are initialised
with (normal, std 0.02; norm gains 1, biases 0), generated here from the seed
itself: nothing is read from the served program.

``mode="fp8"`` is the control: every matmul operand (weights, activations,
attention scores' q/k and the probabilities/v pair) is rounded to float8
e4m3 with one amax scale per tensor before a float32 product.  It stands for
the precision one step below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
E4M3_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class DenseSpec:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm: str            # "rms" | "ln"
    norm_eps: float
    gated: bool          # SwiGLU (True) or GELU-tanh MLP (False)
    qkv_bias: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Rows of the embedding table as initialised (vocab rounded up to 256)."""
        return -(-self.vocab // 256) * 256

    @classmethod
    def from_config(cls, cfg: dict) -> "DenseSpec":
        """From a configuration file of ``bench/configs`` (Hugging Face keys)."""
        act = cfg["hidden_act"]
        if act not in ("silu", "gelu_pytorch_tanh"):
            raise ValueError(f"unsupported hidden_act {act!r}")
        ln = "norm_epsilon" in cfg
        return cls(
            n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            norm="ln" if ln else "rms",
            norm_eps=float(cfg["norm_epsilon"] if ln else cfg["rms_norm_eps"]),
            gated=act == "silu",
        )


# --------------------------------------------------------------------------- #
# weights from the seed
# --------------------------------------------------------------------------- #
def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * INIT_STD


def _norm_params(spec: DenseSpec):
    g = jnp.ones((spec.d_model,), jnp.float32)
    return {"g": g, "b": jnp.zeros_like(g)} if spec.norm == "ln" else {"g": g}


@functools.partial(jax.jit, static_argnums=0)
def embedding(spec: DenseSpec, seed_key) -> jax.Array:
    """(vocab, d) float32: the embedding table, padding rows dropped."""
    ks = jax.random.split(seed_key, 8)
    return _normal(ks[0], (spec.padded_vocab, spec.d_model))[: spec.vocab]


@functools.partial(jax.jit, static_argnums=0)
def layer_weights(spec: DenseSpec, seed_key, i) -> dict:
    """Weights of layer ``i`` (a traced index: one program for every layer)."""
    ks = jax.random.split(seed_key, 8)
    key = jax.random.split(ks[1], spec.n_layers)[i]
    k_attn, k_ffn = jax.random.split(key)
    a = jax.random.split(k_attn, 4)
    d, hd = spec.d_model, spec.head_dim
    w = {
        "wq": _normal(a[0], (d, spec.n_heads * hd)),
        "wk": _normal(a[1], (d, spec.n_kv * hd)),
        "wv": _normal(a[2], (d, spec.n_kv * hd)),
        "wo": _normal(a[3], (spec.n_heads * hd, d)),
        "ln1": _norm_params(spec),
        "ln2": _norm_params(spec),
    }
    if spec.qkv_bias:
        w["bq"] = jnp.zeros((spec.n_heads * hd,), jnp.float32)
        w["bk"] = jnp.zeros((spec.n_kv * hd,), jnp.float32)
        w["bv"] = jnp.zeros((spec.n_kv * hd,), jnp.float32)
    f = jax.random.split(k_ffn, 3)
    w["up"] = _normal(f[0], (d, spec.d_ff))
    w["down"] = _normal(f[1], (spec.d_ff, d))
    if spec.gated:
        w["gate"] = _normal(f[2], (d, spec.d_ff))
    return w


# --------------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------------- #
def _fp8(t: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one amax scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / E4M3_MAX
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec_str: str, a, b, mode: str):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec_str, a, b, precision=HIGHEST)


def _norm(spec: DenseSpec, x, p):
    if spec.norm == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + spec.norm_eps) * p["g"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + spec.norm_eps) * p["g"] + p["b"]


def _rope(x, theta: float):
    """x: (S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(spec: DenseSpec, h, w, mode: str):
    """h: (S, d) one sequence -> (S, d) attention output before ``wo``."""
    s = h.shape[0]
    hd, g = spec.head_dim, spec.n_heads // spec.n_kv
    q = _mm("sd,de->se", h, w["wq"], mode)
    k = _mm("sd,de->se", h, w["wk"], mode)
    v = _mm("sd,de->se", h, w["wv"], mode)
    if spec.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(s, spec.n_heads, hd), spec.rope_theta)
    k = _rope(k.reshape(s, spec.n_kv, hd), spec.rope_theta)
    v = v.reshape(s, spec.n_kv, hd)
    k = jnp.repeat(k, g, axis=1)          # query head h reads kv head h // g
    v = jnp.repeat(v, g, axis=1)
    sc = _mm("qhd,khd->hqk", q, k, mode) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return _mm("hqk,khd->qhd", p, v, mode).reshape(s, spec.n_heads * hd)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(spec: DenseSpec, x, w, mode: str):
    """One residual block over a batch of whole sequences x: (B, S, d)."""
    def attn_one(xb):
        return _mm("sd,de->se", _attention(spec, _norm(spec, xb, w["ln1"]), w, mode),
                   w["wo"], mode)

    x = x + jax.lax.map(attn_one, x)
    h = _norm(spec, x, w["ln2"])
    up = _mm("bsd,df->bsf", h, w["up"], mode)
    if spec.gated:
        act = jax.nn.silu(_mm("bsd,df->bsf", h, w["gate"], mode)) * up
    else:
        act = jax.nn.gelu(up, approximate=True)
    return x + _mm("bsf,fd->bsd", act, w["down"], mode)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head(spec: DenseSpec, x, pos, table, final, mode: str):
    """Final norm and logits at ``pos`` (B, P) of x (B, S, d) -> (B, P, vocab)."""
    h = jnp.take_along_axis(x, pos[..., None], axis=1)
    return _mm("bpd,vd->bpv", _norm(spec, h, final), table, mode)


def logits_at(spec: DenseSpec, seed: int, tokens: np.ndarray, pos: np.ndarray,
              mode: str = "f32") -> jax.Array:
    """Reference logits (B, P, vocab) at positions ``pos`` (B, P) of the
    token sequences ``tokens`` (B, S); the logits at position ``t`` predict
    token ``t + 1``.  Padding after a sequence's end does not reach earlier
    positions (causal)."""
    if mode not in ("f32", "fp8"):
        raise ValueError(f"unknown reference mode {mode!r}")
    key = jax.random.key(seed)
    table = embedding(spec, key)
    x = table[jnp.asarray(tokens, jnp.int32)]
    for i in range(spec.n_layers):
        x = _layer(spec, x, layer_weights(spec, key, jnp.int32(i)), mode)
    final = _norm_params(spec)
    return _head(spec, x, jnp.asarray(pos, jnp.int32), table, final, mode)
