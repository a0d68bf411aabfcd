"""Plain reference of ``unest_wholebrain``: UNesT (a NesT-3D encoder of
block attention, a UNETR-style conv decoder) over MONAI-style sliding
windows, with each window's logits added into one buffer.

Model (configuration ``stages.unest_wholebrain``, widths D, heads H,
features F, classes C, window R): a P-stride patch embedding; per level a
3³ conv with bias, LayerNorm and 3³ max-pool of stride 2 between levels,
the level's positional embedding over its blocks of (R/P/n)³ tokens (n =
4, 2, 1 blocks an axis), pre-LN layers of unmasked multi-head attention
inside each block and a GELU MLP; a final LayerNorm. Decoder: residual
blocks (conv 3³, instance norm, LeakyReLU; a 1³ conv on the skip where the
widths differ), transposed convs of kernel and stride 2 with skips from the
input, the up-sampled embedding, levels 0, 1 and 2, and a 1³ conv onto C
classes. Sliding window: zero mean and unit std, zeros padded half before
and half after an axis shorter than R, starts every floor(R(1 - overlap))
with the last clipped to the end, constant weights, the average's arg-max
and top-1 softmax probability.

The weights are drawn here from the configuration's ``weight_seed`` with
the draws the stage documents: conv weights in parameter order as slices of
one standard normal draw of ``fold_in(key, 0)`` scaled by sqrt(2/fan-in),
linear and positional weights as slices of one normal draw truncated at ±2
of ``fold_in(key, 1)`` scaled by 0.02, LayerNorm gains 1, biases 0. Nothing
is taken from the program.

float32 with every contraction at HIGHEST precision, one window at a time,
each convolution a sum of one matmul per tap; the blend adds each window
into a float32 buffer on the device.
``lowp=True`` is the control: the operands of every contraction rounded
through float8 (e4m3) and the activations through bfloat16, below the
stage's bfloat16.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

OUTPUTS = ("segmentation", "label_confidence")
# confidence: largest |p - p_ref| / max p_ref over voxels, p the top-1
# softmax probability; labels: share of decided voxels (reference top-1
# minus top-2 probability above 2 * the confidence limit * max p_ref) whose
# label differs (exact). Set from full-size chip readings: the program's
# about 0.023, the control's far above; readings in PERF.md.
LIMITS = {"confidence": 0.1, "labels": 0.0}
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
DIMS = ("NDHWC", "DHWIO", "NDHWC")


def _params(st: dict):
    """(name, shape, draw) of every parameter, in the stage's order."""
    P, F, C = st["patch"], st["feature_size"], st["classes"]
    D, depths = st["embed_dims"], st["depths"]
    side = st["roi"] // P // 4
    out = [("embed.w", (P, P, P, 1, D[0]), "conv"),
           ("embed.b", (D[0],), "zeros")]
    for lvl in range(3):
        d = D[lvl]
        if lvl:
            out += [(f"agg{lvl}.w", (3, 3, 3, D[lvl - 1], d), "conv"),
                    (f"agg{lvl}.b", (d,), "zeros"),
                    (f"agg{lvl}.ln.g", (d,), "ones"),
                    (f"agg{lvl}.ln.b", (d,), "zeros")]
        out.append((f"l{lvl}.pos", (8 ** (2 - lvl), side ** 3, d), "linear"))
        m = st["mlp_ratio"] * d
        for i in range(depths[lvl]):
            p = f"l{lvl}.{i}."
            out += [(p + "ln1.g", (d,), "ones"), (p + "ln1.b", (d,), "zeros"),
                    (p + "qkv", (3, d, d), "linear"),
                    (p + "proj", (d, d), "linear"),
                    (p + "proj.b", (d,), "zeros"),
                    (p + "ln2.g", (d,), "ones"), (p + "ln2.b", (d,), "zeros"),
                    (p + "fc1", (d, m), "linear"), (p + "fc1.b", (m,), "zeros"),
                    (p + "fc2", (m, d), "linear"), (p + "fc2.b", (d,), "zeros")]
    out += [("norm.g", (D[2],), "ones"), ("norm.b", (D[2],), "zeros")]

    def res(name, cin, cout):
        r = [(f"{name}.conv1", (3, 3, 3, cin, cout), "conv"),
             (f"{name}.conv2", (3, 3, 3, cout, cout), "conv")]
        if cin != cout:
            r.append((f"{name}.conv3", (1, 1, 1, cin, cout), "conv"))
        return r

    out += res("enc0", 1, F)
    out += [("enc1.up", (D[0], 2, 2, 2, 2 * F), "conv")] + \
        res("enc1", 2 * F, 2 * F)
    out += res("enc2", D[0], 2 * F) + res("enc3", D[1], 4 * F)
    out.append(("dec4", (3, 3, 3, D[2], 32 * F), "conv"))
    cin = 32 * F
    for name, cout, skip in _ups(st):
        out += [(f"{name}.up", (cin, 2, 2, 2, cout), "conv")]
        out += res(name, cout + skip, cout)
        cin = cout
    out += [("head.w", (F, C), "conv"), ("head.b", (C,), "zeros")]
    return out


def _ups(st):
    F, D = st["feature_size"], st["embed_dims"]
    return [("up5", 16 * F, D[2]), ("up4", 8 * F, 4 * F),
            ("up3", 4 * F, 2 * F), ("up2", 2 * F, 2 * F), ("up1", F, F)]


def _items(st: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in st.items()))


@functools.partial(jax.jit, static_argnames=("items",))
def _draw(seed, *, items):
    st = dict(items)
    shapes = _params(st)
    key = jax.random.PRNGKey(seed)
    n = {d: sum(math.prod(s) for _, s, k in shapes if k == d)
         for d in ("conv", "linear")}
    flat = {"conv": jax.random.normal(jax.random.fold_in(key, 0),
                                      (n["conv"],), F32),
            "linear": 0.02 * jax.random.truncated_normal(
                jax.random.fold_in(key, 1), -2.0, 2.0, (n["linear"],), F32)}
    at = {"conv": 0, "linear": 0}
    w = {}
    for name, shape, kind in shapes:
        if kind in flat:
            size = math.prod(shape)
            a = flat[kind][at[kind]:at[kind] + size].reshape(shape)
            at[kind] += size
            if kind == "conv":
                fan = shape[0] if name.endswith(".up") else \
                    math.prod(shape[:-1])
                a = a * math.sqrt(2.0 / fan)
            w[name] = a
        else:
            w[name] = (jnp.ones if kind == "ones" else jnp.zeros)(shape, F32)
    return w


def weights(st: dict) -> dict:
    return _draw(st["weight_seed"], items=_items(st))


# The forward is plain Python over jitted pieces (a residual block, a
# transformer layer, ...), one program each: compiled as one float32
# program at HIGHEST precision, the window took the TPU compiler more than
# 15 minutes and more host memory than a one-chip machine has.

def _q(a, lowp):                    # a contraction's operand
    return a.astype(jnp.float8_e4m3fn).astype(F32) if lowp else a


def _act(a, lowp):                  # an activation between layers
    return a.astype(jnp.bfloat16).astype(F32) if lowp else a


def _ein(spec, a, b, lowp):
    return _act(jnp.einsum(spec, _q(a, lowp), _q(b, lowp), precision=HI),
                lowp)


def _conv(a, k, lowp, stride=1, pad=None):
    """out[x] = sum over taps t and channels c of a[stride x + t - pad, c]
    k[t, c]: one matmul per tap (the TPU compiler takes minutes and GBs for
    some float32 convolutions at HIGHEST, and seconds for these)."""
    K = k.shape[0]
    pad = (K - 1) // 2 if pad is None else pad
    p = jnp.pad(_q(a, lowp), [(pad, pad)] * 3 + [(0, 0)])
    n = [(s + 2 * pad - K) // stride + 1 for s in a.shape[:3]]
    out = 0.0
    for i in range(K):
        for j in range(K):
            for l in range(K):
                tap = p[i:i + stride * (n[0] - 1) + 1:stride,
                        j:j + stride * (n[1] - 1) + 1:stride,
                        l:l + stride * (n[2] - 1) + 1:stride]
                out = out + jnp.einsum("xyzc,co->xyzo", tap,
                                       _q(k[i, j, l], lowp), precision=HI)
    return _act(out, lowp)


def _ln(a, g, b, eps, lowp):
    m = a.mean(-1, keepdims=True)
    v = ((a - m) ** 2).mean(-1, keepdims=True)
    return _act((a - m) / jnp.sqrt(v + eps) * g + b, lowp)


def _inorm(a, eps, lowp):
    m = a.mean((0, 1, 2), keepdims=True)
    v = ((a - m) ** 2).mean((0, 1, 2), keepdims=True)
    return _act((a - m) / jnp.sqrt(v + eps), lowp)


def _leaky(a, slope):
    return jnp.where(a >= 0, a, slope * a)


@functools.partial(jax.jit, static_argnames=("eps", "slope", "lowp"))
def _res(w, a, *, eps, slope, lowp):
    """MONAI's UnetResBlock; ``w`` holds conv1, conv2 and, where the widths
    differ, conv3."""
    h = _conv(_leaky(_inorm(_conv(a, w["conv1"], lowp), eps, lowp), slope),
              w["conv2"], lowp)
    s = _inorm(_conv(a, w["conv3"], lowp), eps, lowp) if "conv3" in w \
        else a
    return _leaky(_inorm(h, eps, lowp) + s, slope)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _up(a, k, *, lowp):
    """Transposed conv of kernel and stride 2: out[2i + o] = sum_c a[i, c]
    k[c, o] on each axis."""
    X, Y, Z, _ = a.shape
    y = _ein("xyzc,cijko->xyzijko", a, k, lowp)
    return y.transpose(0, 3, 1, 4, 2, 5, 6).reshape(2 * X, 2 * Y, 2 * Z, -1)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "lowp"))
def _layer(w, z, *, heads, eps, lowp):
    """One pre-LN transformer layer over blocks z (n, T, d)."""
    T, d = z.shape[1], z.shape[2]
    dh = d // heads
    y = _ln(z, w["ln1.g"], w["ln1.b"], eps, lowp)
    qh, kh, vh = (_ein("ntd,de->nte", y, w["qkv"][i], lowp)
                  .reshape(-1, T, heads, dh) for i in range(3))
    s = jnp.einsum("nqhd,nkhd->nhqk", _q(qh, lowp), _q(kh, lowp),
                   precision=HI) / math.sqrt(dh)
    a = _act(jax.nn.softmax(s, axis=-1), lowp)
    o = _ein("nhqk,nkhd->nqhd", a, vh, lowp).reshape(-1, T, d)
    z = _act(z + _ein("nte,ed->ntd", o, w["proj"], lowp) + w["proj.b"], lowp)
    y = _ln(z, w["ln2.g"], w["ln2.b"], eps, lowp)
    y = _act(jax.nn.gelu(_ein("ntd,df->ntf", y, w["fc1"], lowp) + w["fc1.b"],
                         approximate=False), lowp)
    return _act(z + _ein("ntf,fd->ntd", y, w["fc2"], lowp) + w["fc2.b"],
                lowp)


@functools.partial(jax.jit, static_argnames=("patch", "lowp"))
def _embed(x, k, b, *, patch, lowp):
    return _act(_conv(_act(x[..., None], lowp), k, lowp, stride=patch,
                      pad=0) + b, lowp)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _aggregate(w, h, *, eps, lowp):
    """Conv 3^3 with bias, LayerNorm, max-pool 3^3 of stride 2."""
    h = _conv(h, w["w"], lowp) + w["b"]
    h = _ln(h, w["ln.g"], w["ln.b"], eps, lowp)
    return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (3, 3, 3, 1),
                                 (2, 2, 2, 1),
                                 [(1, 1), (1, 1), (1, 1), (0, 0)])


@functools.partial(jax.jit, static_argnames=("n", "lowp"))
def _blocks(h, pos, *, n, lowp):
    """(G,G,G,d) -> (n^3, (G/n)^3, d), plus the positional embedding."""
    G, d = h.shape[0], h.shape[-1]
    s = G // n
    a = h.reshape(n, s, n, s, n, s, d).transpose(0, 2, 4, 1, 3, 5, 6)
    return _act(a.reshape(n ** 3, s ** 3, d) + pos, lowp)


def _unblocks(a, n):
    s = round(a.shape[1] ** (1 / 3))
    d = a.shape[-1]
    a = a.reshape(n, n, n, s, s, s, d).transpose(0, 3, 1, 4, 2, 5, 6)
    return a.reshape(n * s, n * s, n * s, d)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _final_norm(h, g, b, *, eps, lowp):
    return _ln(h, g, b, eps, lowp)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _dec4(h, k, *, lowp):
    return _conv(h, k, lowp, stride=2)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _head(d, k, b, *, lowp):
    return _ein("xyzf,fc->xyzc", d, k, lowp) + b


def _window(w, x, st: dict, lowp: bool):
    """Logits (R, R, R, C) of one normalised window x (R, R, R)."""
    eps, ieps, slope = st["ln_eps"], st["in_eps"], st["leaky_slope"]

    def part(prefix):
        return {k[len(prefix):]: v for k, v in w.items()
                if k.startswith(prefix)}

    def res(name, a):
        return _res({k: w[f"{name}.{k}"] for k in ("conv1", "conv2", "conv3")
                     if f"{name}.{k}" in w}, a, eps=ieps, slope=slope,
                    lowp=lowp)

    t = _embed(x, w["embed.w"], w["embed.b"], patch=st["patch"], lowp=lowp)
    h, levels = t, []
    for lvl in range(3):
        if lvl:
            h = _aggregate(part(f"agg{lvl}."), h, eps=eps, lowp=lowp)
        n = 2 ** (2 - lvl)
        z = _blocks(h, w[f"l{lvl}.pos"], n=n, lowp=lowp)
        for i in range(st["depths"][lvl]):
            z = _layer(part(f"l{lvl}.{i}."), z, heads=st["num_heads"][lvl],
                       eps=eps, lowp=lowp)
        h = _unblocks(z, n)
        levels.append(h)
    h2 = _final_norm(h, w["norm.g"], w["norm.b"], eps=eps, lowp=lowp)
    x1 = _act(x[..., None], lowp)
    e0 = res("enc0", x1)
    e1 = res("enc1", _up(t, w["enc1.up"], lowp=lowp))
    e2 = res("enc2", levels[0])
    e3 = res("enc3", levels[1])
    d = _dec4(h2, w["dec4"], lowp=lowp)
    for (name, _, _), skip in zip(_ups(st), (h2, e3, e2, e1, e0)):
        d = res(name, jnp.concatenate([_up(d, w[f"{name}.up"], lowp=lowp),
                                       skip], -1))
    return _head(d, w["head.w"], w["head.b"], lowp=lowp)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _add(buf, cnt, logits, at):
    """Add one window's logits (R, R, R, C) at ``at`` into the (C, ...)
    buffer, and 1 into the count map."""
    R = logits.shape[0]
    pos = (0, at[0], at[1], at[2])
    cur = jax.lax.dynamic_slice(buf, pos, (logits.shape[-1], R, R, R))
    buf = jax.lax.dynamic_update_slice(
        buf, cur + jnp.moveaxis(logits, -1, 0), pos)
    cur = jax.lax.dynamic_slice(cnt, at, (R, R, R))
    return buf, jax.lax.dynamic_update_slice(cnt, cur + 1.0, at)


def starts(shape, roi: int, overlap: float) -> list:
    """Window starts over a volume of at least ``roi`` each axis."""
    axes = []
    for s in shape:
        step = roi if s == roi else max(int(roi * (1 - overlap)), 1)
        first_end = next(i for i in range(s) if i * step + roi >= s)
        axes.append(sorted({min(i * step, s - roi)
                            for i in range(first_end + 1)}))
    return [(a, b, c) for a in axes[0] for b in axes[1] for c in axes[2]]


@functools.partial(jax.jit, static_argnames=("crop",))
def _finish(buf, cnt, *, crop):
    """Label, top-1 probability and top-1 minus top-2 probability of the
    average over the input grid, reduced over classes without a copy of the
    buffer."""
    box = tuple(slice(a, a + s) for a, s in crop)
    avg = buf[(slice(None),) + box] / cnt[box]
    label = jnp.argmax(avg, 0)
    top = avg.max(0)
    conf = 1.0 / jnp.exp(avg - top).sum(0)
    cls = jnp.arange(avg.shape[0])[:, None, None, None]
    second = jnp.where(cls == label, -jnp.inf, avg).max(0)
    return label.astype(jnp.uint8), conf, conf * (1 - jnp.exp(second - top))


def reference(inputs: dict, stage: dict, lowp: bool = False) -> dict:
    vol = np.asarray(inputs["T1w"], np.float64)
    R = stage["roi"]
    v = ((vol - vol.mean()) / (vol.std() + 1e-6)).astype(np.float32)
    pads = [(max(R - s, 0) // 2, max(R - s, 0) - max(R - s, 0) // 2)
            for s in v.shape]
    v = np.pad(v, pads)
    with jax.default_matmul_precision("highest"):
        w = weights(stage)
        buf = jnp.zeros((stage["classes"],) + v.shape, F32)
        cnt = jnp.zeros(v.shape, F32)
        for a, b, c in starts(v.shape, R, stage["overlap"]):
            logits = _window(w, jnp.asarray(v[a:a + R, b:b + R, c:c + R]),
                             stage, lowp)
            buf, cnt = _add(buf, cnt, logits, jnp.array([a, b, c]))
        label, conf, margin = _finish(
            buf, cnt, crop=tuple((p, s) for (p, _), s in zip(pads,
                                                             vol.shape)))
        del buf, cnt
    return {"segmentation": np.asarray(label),
            "label_confidence": np.asarray(conf),
            "margin": np.asarray(margin)}


def gaps(got: dict, want: dict, stage: dict) -> dict:
    conf = np.asarray(got["label_confidence"], np.float64)
    ref = want["label_confidence"].astype(np.float64)
    seg = np.asarray(got["segmentation"])
    if conf.shape != ref.shape or seg.shape != want["segmentation"].shape:
        return {"confidence": float("inf"), "labels": 1.0}
    scale = float(ref.max()) or 1.0
    decided = want["margin"] > 2 * LIMITS["confidence"] * scale
    wrong = (seg != want["segmentation"]) & decided
    print(f"unest_wholebrain: {int(decided.sum())} of {decided.size} voxels "
          f"decided ({decided.mean():.6f}), max p_ref {scale:.6f}, median "
          f"p_ref {float(np.median(ref)):.6f}", file=sys.stderr, flush=True)
    return {"confidence": float(np.max(np.abs(conf - ref))) / scale,
            "labels": float(wrong.sum()) / max(int(decided.sum()), 1)}
