"""Plain Qwen2.5 decoder (``Qwen2ForCausalLM``), for checking the served path.

The model, as arXiv:2412.15115 and the published ``config.json``
describe it, with ``rms(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(the scale stored as its offset from 1, as the program stores it):
token embedding ``E``, then per layer

    q, k, v = rms(x, ln1) W_{q,k,v} + b_{q,k,v}     grouped-query heads
    h = x + Attn(q, k, v) W_o      causal, rotary positions on q and k
                                   (half-split, theta from the config),
                                   softmax(q k^T / sqrt(hd)), each
                                   key-value head shared by H / KVH
                                   query heads
    x = h + W_down (silu(rms(h, ln2) W_gate) * rms(h, ln2) W_up)

and ``logits = rms(x, final) E^T`` (the output head tied to the
embedding).

:func:`make_params` makes the weights from a key, on the device, in one
jitted call and in the layout the program's ``ServeEngine`` takes
(stacked over layers, in the configuration's ``param_dtype``).  The
benchmark makes them; the program and the reference both read them.

:func:`logits` is the reference: a full causal forward over one token
sequence in float32 under ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no batching, one layer at a time (a scan over the
stacked weights), padded to a fixed length so every sequence shares one
program (causal masking keeps the padding out of every earlier
position).  With ``fp8=True`` every matrix product rounds both operands
to float8 e4m3 with a per-tensor scale and accumulates in float32: the
control, one precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    d, h, kvh, f, n = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"], m["n_layers"]
    hd = d // h
    v = -(-m["vocab_size"] // m["vocab_pad_to"]) * m["vocab_pad_to"]
    return {
        "embed": ((v, d), 1.0 / math.sqrt(d)),
        "final_norm": {"scale": ((d,), 0.1)},
        "blocks": {
            "ln1": {"scale": ((n, d), 0.1)},
            "attn": {
                "wq": ((n, d, h, hd), 1.0 / math.sqrt(d)),
                "wk": ((n, d, kvh, hd), 1.0 / math.sqrt(d)),
                "wv": ((n, d, kvh, hd), 1.0 / math.sqrt(d)),
                "wo": ((n, h, hd, d), 1.0 / math.sqrt(d)),
                "bq": ((n, h, hd), 0.1),
                "bk": ((n, kvh, hd), 0.1),
                "bv": ((n, kvh, hd), 0.1),
            },
            "ln2": {"scale": ((n, d), 0.1)},
            "mlp": {
                "w_gate": ((n, d, f), 1.0 / math.sqrt(d)),
                "w_up": ((n, d, f), 1.0 / math.sqrt(d)),
                "w_down": ((n, f, d), 1.0 / math.sqrt(f)),
            },
        },
    }


def make_params(model: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Normal weights, each leaf scaled as :func:`_shapes` says, from
    ``key``: one jitted call that writes every leaf on the device."""
    spec = _shapes(model)
    leaves, tree = jax.tree.flatten(spec, is_leaf=lambda x: isinstance(x, tuple))
    dtype = jnp.dtype(model["param_dtype"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return [std * jax.random.normal(k, shape, dtype)
                for k, (shape, std) in zip(keys, leaves, strict=True)]

    return jax.tree.unflatten(tree, make(key))


def _q8(x):
    """``x`` rounded to float8 e4m3 with a per-tensor scale, as float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """x: [T, H, hd]; rotate the two halves of each head by position."""
    t, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _forward(params, tokens, at, *, eps: float, theta: float, fp8: bool):
    """Logits at positions ``at`` ([P] int32) of ``tokens`` ([T] int32):
    [P, V]."""
    f32 = jnp.float32
    embed = params["embed"].astype(f32)
    x = embed[tokens]
    t = tokens.shape[0]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], eps)
        q = _rope(_mm("td,dhk->thk", h, a["wq"], fp8) + a["bq"], theta)
        k = _rope(_mm("td,dhk->thk", h, a["wk"], fp8) + a["bk"], theta)
        v = _mm("td,dhk->thk", h, a["wv"], fp8) + a["bv"]
        kvh, hd = k.shape[1], k.shape[2]
        q = q.reshape(t, kvh, -1, hd)  # query head h = kv * G + g
        s = _mm("qcgk,sck->cgqs", q, k, fp8) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = _mm("cgqs,sck->qcgk", jax.nn.softmax(s, axis=-1), v, fp8)
        x = x + _mm("thk,hkd->td", o.reshape(t, -1, hd), a["wo"], fp8)
        h = _rms(x, p["ln2"]["scale"], eps)
        gate = _mm("td,df->tf", h, p["mlp"]["w_gate"], fp8)
        up = _mm("td,df->tf", h, p["mlp"]["w_up"], fp8)
        return x + _mm("tf,fd->td", jax.nn.silu(gate) * up, p["mlp"]["w_down"], fp8), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x[at], params["final_norm"]["scale"].astype(f32), eps)
    return _mm("td,vd->tv", x, embed, fp8)


def logits(params, model: Dict[str, Any], tokens, pad_to: int,
           at: Sequence[int] | None = None, *, fp8: bool = False):
    """The reference's logits (float32) of one sequence at the positions
    ``at`` (every position by default), the sequence padded to
    ``pad_to``: ``[len(at), V]``."""
    n = int(tokens.shape[0])
    at = jnp.arange(n, dtype=jnp.int32) if at is None else jnp.asarray(at, jnp.int32)
    padded = jnp.zeros((pad_to,), jnp.int32).at[:n].set(jnp.asarray(tokens, jnp.int32))
    with jax.default_matmul_precision("highest"):
        return _forward(params, padded, at, eps=float(model["norm_eps"]),
                        theta=float(model["rope_theta"]), fp8=fp8)
