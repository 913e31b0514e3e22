"""Plain float32 reference of the dense decoder the benchmark serves.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no cache, no
batching: token embedding, then per layer RMSNorm -> grouped-query
attention with rotary positions (the half-split "rotate half" form,
frequencies ``theta ** (-i / (head_dim / 2))``) -> residual -> RMSNorm ->
MLP (SwiGLU, or the two-matrix tanh-GELU MLP of the GPT-BigCode line) ->
residual, then a final RMSNorm and the output table.  GQA covers MQA
(one KV head).  It imports nothing of the program: it reads the weights
that ``weights.py`` made, by the names of the served tree.

The whole sequence runs in one pass, layer after layer inside a scan
(one layer's weights in float32 at a time), with attention taken over
blocks of queries so that it fits beside the served weights.  The
sequence is padded at its end to a multiple of ``PAD``: causal attention
keeps the padding out of every real position, and the padding keeps the
number of compiled shapes small.

``precision="int8"`` is the control: every projection's weights are
quantized per output channel and its input per token to symmetric int8
(W8A8), the step below the served bfloat16 that a later change could be
tempted to take.  Attention itself stays in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 512          # sequence padding (positions)
ROW_PAD = 128      # padding of the rows whose logits are returned
Q_BLOCK = 512


def _int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "int8":
        x, w = _int8(x, -1), _int8(w, -2)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(q, k, v):
    """q [s, h, hd], k/v [s, g, hd] -> [s, h * hd], causal."""
    s, h, hd = q.shape
    g = k.shape[1]
    k = jnp.repeat(k, h // g, axis=1)
    v = jnp.repeat(v, h // g, axis=1)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))
    return out.reshape(s, h * hd)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _forward(params, tokens, rows, *, cfg: tuple, precision: str):
    (h, g, hd, vocab, mlp, theta, eps) = cfg
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    s = x.shape[0]

    def layer(x, p):
        a = _rms(x, p["attn_norm"]["scale"], eps)
        q = _mm(a, p["attn"]["q"]["w"], precision).reshape(s, h, hd)
        k = _mm(a, p["attn"]["k"]["w"], precision).reshape(s, g, hd)
        v = _mm(a, p["attn"]["v"]["w"], precision).reshape(s, g, hd)
        o = _attention(_rope(q, theta), _rope(k, theta), v)
        x = x + _mm(o, p["attn"]["o"]["w"], precision)
        m = _rms(x, p["mlp_norm"]["scale"], eps)
        if mlp == "swiglu":
            f = jax.nn.silu(_mm(m, p["mlp"]["gate"]["w"], precision)) * \
                _mm(m, p["mlp"]["up"]["w"], precision)
        else:
            f = jax.nn.gelu(_mm(m, p["mlp"]["up"]["w"], precision), approximate=True)
        return x + _mm(f, p["mlp"]["down"]["w"], precision), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x[rows], params["final_norm"]["scale"], eps)
    table = params.get("lm_head", params["embed"])["table"]
    return _mm(x, table.T, precision)[:, :vocab]


def logits(params, sizes: dict, tokens: np.ndarray, rows: np.ndarray,
           precision: str = "float32") -> np.ndarray:
    """Float32 logits ``[len(rows), vocab]`` of the causal forward pass over
    ``tokens``, at positions ``rows``."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD) * PAD, np.int32)
    padded[:n] = tokens
    cfg = (sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"],
           sizes["vocab_size"], sizes["mlp_type"], float(sizes["rope_theta"]),
           float(sizes["norm_eps"]))
    want = np.zeros(-(-len(rows) // ROW_PAD) * ROW_PAD, np.int32)
    want[:len(rows)] = rows
    out = _forward(params, jnp.asarray(padded), jnp.asarray(want),
                   cfg=cfg, precision=precision)
    return np.asarray(out)[:len(rows)]
