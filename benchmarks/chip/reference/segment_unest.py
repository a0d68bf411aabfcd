"""Plain reference of ``segment_unest``: 4-voxel patches, a linear patch
embedding, a pre-norm transformer (RMSNorm, rotary positions, grouped-query
attention under a causal mask, GELU MLP), a final RMSNorm and a class head;
the label of each patch is its arg-max class, painted over its voxels.

The weights are drawn here from the configuration's weight seed with the
same ``jax.random`` draws the stage makes (normal, 0.02, residual outputs
scaled by 1/sqrt(2 * layers)); nothing is taken from the program.

float32 with every contraction at HIGHEST precision; attention runs over
blocks of queries against all keys. ``lowp=True`` is the control: the
operands of every contraction in float8 (e4m3) and the activations in
bfloat16, the step below the stage's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

OUTPUTS = ("class_logits", "segmentation")
# logits: largest |program - reference| / max |reference|; labels: share of
# voxels in decided patches whose label differs (exact). Readings in
# PERF.md.
LIMITS = {"logits": 4e-2, "labels": 0.0}
Q_BLOCK = 512


def weights(seg: dict) -> dict:
    D, H, KV, Dh = seg["d_model"], seg["n_heads"], seg["n_kv_heads"], \
        seg["d_head"]
    F, L, V, P = seg["d_ff"], seg["n_layers"], seg["vocab"], seg["patch"]
    ks = jax.random.split(jax.random.PRNGKey(seg["weight_seed"]), 8)
    ka = jax.random.split(ks[2], 4)
    km = jax.random.split(ks[3], 2)
    out = 0.02 / math.sqrt(2 * L)

    def n(k, shape, scale=0.02):
        return scale * jax.random.normal(k, shape, jnp.float32)

    return {"head": n(ks[1], (D, V)),
            "wq": n(ka[0], (L, D, H * Dh)), "wk": n(ka[1], (L, D, KV * Dh)),
            "wv": n(ka[2], (L, D, KV * Dh)),
            "wo": n(ka[3], (L, H * Dh, D), out),
            "w1": n(km[0], (L, D, F)), "w2": n(km[1], (L, F, D), out),
            "embed": jax.random.normal(
                jax.random.PRNGKey(seg["weight_seed"] + 1), (P ** 3, D),
                jnp.float32) / P ** 1.5}


def _patches(vol, p):
    X, Y, Z = (s // p for s in vol.shape)
    x = vol[:X * p, :Y * p, :Z * p].reshape(X, p, Y, p, Z, p)
    x = x.transpose(0, 2, 4, 1, 3, 5).reshape(X * Y * Z, p ** 3)
    return (x - x.mean()) / (x.std() + 1e-6)


@functools.partial(jax.jit, static_argnames=("seg_items", "lowp"))
def _forward(vol, w, *, seg_items, lowp: bool):
    seg = dict(seg_items)
    H, KV, Dh, L = seg["n_heads"], seg["n_kv_heads"], seg["d_head"], \
        seg["n_layers"]
    C, eps = seg["n_classes"], seg["norm_eps"]
    act = jnp.bfloat16 if lowp else jnp.float32
    opd = jnp.float8_e4m3fn if lowp else jnp.float32
    hi = jax.lax.Precision.HIGHEST

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(opd), b.astype(opd), precision=hi,
                          preferred_element_type=jnp.float32).astype(act)

    def norm(x):
        x32 = x.astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (x32 * r).astype(act)

    x = mm("tp,pd->td", _patches(vol.astype(jnp.float32), seg["patch"]),
           w["embed"])
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.float32)
    freqs = 1.0 / seg["rope_theta"] ** (jnp.arange(0, Dh, 2,
                                                   dtype=jnp.float32) / Dh)
    ang = (pos[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rope(t):
        a, b = jnp.split(t.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                               -1).astype(act)

    bq = math.gcd(T, Q_BLOCK)
    k_pos = jnp.arange(T)
    scale = 1.0 / math.sqrt(Dh)
    for layer in range(L):
        h = norm(x)
        q = rope(mm("td,de->te", h, w["wq"][layer]).reshape(T, H, Dh))
        k = rope(mm("td,de->te", h, w["wk"][layer]).reshape(T, KV, Dh))
        v = mm("td,de->te", h, w["wv"][layer]).reshape(T, KV, Dh)
        k = jnp.repeat(k, H // KV, axis=1)         # query head h -> kv h // G
        v = jnp.repeat(v, H // KV, axis=1)

        def block(args):
            qb, q0 = args
            s = mm("qhd,khd->hqk", qb, k).astype(jnp.float32) * scale
            keep = (q0 + jnp.arange(bq))[:, None] >= k_pos[None, :]
            p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
            return mm("hqk,khd->qhd", p, v)

        o = jax.lax.map(block, (q.reshape(T // bq, bq, H, Dh),
                                jnp.arange(0, T, bq)))
        x = x + mm("te,ed->td", o.reshape(T, H * Dh), w["wo"][layer])
        h = norm(x)
        x = x + mm("tf,fd->td", jax.nn.gelu(mm("td,df->tf", h,
                                                w["w1"][layer])),
                   w["w2"][layer])
    return mm("td,dc->tc", norm(x), w["head"][:, :C]).astype(jnp.float32)


def reference(inputs: dict, stage: dict, lowp: bool = False) -> dict:
    vol = inputs["T1w"]
    items = tuple(sorted((k, v) for k, v in stage.items()
                         if isinstance(v, (int, float, str))))
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(_forward(jnp.asarray(vol, jnp.float32),
                                     weights(stage), seg_items=items,
                                     lowp=lowp))
    p = stage["patch"]
    dims = [s // p for s in vol.shape]
    labels = np.argmax(logits, -1).reshape(dims)
    seg = labels.repeat(p, 0).repeat(p, 1).repeat(p, 2).astype(np.int32)
    return {"class_logits": logits, "segmentation": seg}


def gaps(got: dict, want: dict, stage: dict) -> dict:
    ref = want["class_logits"].astype(np.float64)
    lg = np.asarray(got["class_logits"], np.float64)
    seg = np.asarray(got["segmentation"])
    if lg.shape != ref.shape or seg.shape != want["segmentation"].shape:
        return {"logits": float("inf"), "labels": 1.0}
    scale = float(np.max(np.abs(ref))) or 1.0
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 2 * LIMITS["logits"] * scale
    p = stage["patch"]
    dims = [s // p for s in want["segmentation"].shape]
    decided = decided.reshape(dims).repeat(p, 0).repeat(p, 1).repeat(p, 2)
    wrong = (seg != want["segmentation"]) & decided
    return {"logits": float(np.max(np.abs(lg - ref))) / scale,
            "labels": float(wrong.sum()) / max(int(decided.sum()), 1)}
