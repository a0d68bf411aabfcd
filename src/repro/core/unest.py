"""UNesT whole-brain segmentation by sliding windows (the ``unest_wholebrain``
stage).

Source: Yu et al. 2023, "UNesT: local spatial representation learning with
hierarchical transformer for efficient medical segmentation", Medical Image
Analysis (arXiv:2209.14378), at the settings of the MONAI model-zoo bundle
``wholeBrainSeg_Large_UNEST_segmentation``: 133 labels, patch 4, embed dims
128/256/512, depths 2/2/8, heads 4/8/16, 96³ windows over a 1 mm T1w. The
encoder is NesT-3D (Zhang et al. 2022, "Nested hierarchical transformer").

Notation: a window ``x`` is R³ voxels (R = ``roi``), channels last; B
windows run at once. ``conv_k^s`` is a k³ convolution of stride s padded by
(k-1)/2, ``up`` a transposed convolution of kernel 2 and stride 2
(``out[2i+a] = Σ_c x[i, c] W[c, a]``), ``LN`` LayerNorm over channels
(eps ``ln_eps``, affine), ``IN`` instance norm over each window's voxels
(eps ``in_eps``, no affine), ``σ`` LeakyReLU(``leaky_slope``).

Encoder, levels ℓ = 0 .. L-1 (L = 3), widths D_ℓ, heads H_ℓ, d_head = D_ℓ/H_ℓ:

    t   = conv_P^P(x) + b                      patch embedding, (R/P)³ × D_0
    h_0 = t;   h_ℓ = maxpool_3^2(LN(conv_3^1(h_{ℓ-1}) + b))    for ℓ ≥ 1
    z   = blockify(h_ℓ, n_ℓ) + pos_ℓ           n_ℓ = 2^(L-1-ℓ) blocks an axis
    z  += W_o · softmax(Q Kᵀ / √d_head) V + b_o,  Q, K, V = LN(z) W_{q,k,v}
    z  += W_2 · GELU(W_1 LN(z) + b_1) + b_2    (×depths[ℓ], pre-LN)
    h_ℓ = deblockify(z);   h_{L-1} = LN(h_{L-1})  (the final norm)

Attention runs inside each block of (R/P/n_ℓ)³ tokens (216 at R = 96),
bidirectional, with no mask, no rotary positions and no q/k/v bias. At
R = 96 the levels hold 64, 8 and 1 blocks. ``blockify`` splits each axis
into n_ℓ blocks, orders blocks and the tokens inside a block row-major, and
``pos_ℓ`` (n_ℓ³, tokens, D_ℓ) is indexed in that order.

Decoder, F = ``feature_size`` (the UNETR pattern of the UNesT reference
code as recalled; not checked in this repository):

    Res(y)  = σ(IN(conv_3(σ(IN(conv_3(y))))) + s),  s = IN(conv_1(y)) where
              the channel counts differ, else y     (MONAI UnetResBlock)
    Up(d, e) = Res(concat(up(d), e))
    e0 = Res(x): F at R;          e1 = Res(up(t)): 2F at R/2
    e2 = Res(h_0): 2F at R/4;     e3 = Res(h_1): 4F at R/8
    d  = conv_3^2(h_2): 32F at R/32
    d  = Up(d, h_2): 16F;  Up(d, e3): 8F;  Up(d, e2): 4F;  Up(d, e1): 2F;
    d  = Up(d, e0): F at R;   logits = W_c d + b_c   (C = ``classes``)

Sliding window (MONAI ``sliding_window_inference`` as recalled): the
volume is normalised to zero mean and unit std; an axis shorter than R is
padded with zeros to R, half before and the rest after; window starts lie
every ⌊R(1 - overlap)⌋ voxels with the last start clipped to ``size - R``
(``dense_patch_slices``), 7 × 7 × 4 = 196 windows at 256×256×176. Windows
run ``sw_batch`` at a time; each window's logits are added with constant
weight 1 into a float32 buffer of C × X × Y × Z beside a count map, and the
average, cropped to the input grid, gives the label (arg-max, uint8) and
its confidence (top-1 softmax probability, float32). The buffer is held in
two parts, the first 128 classes minor and the rest class-major
(:func:`blend_parts`), so that a window's update is written in place in
whole rows of the TPU's tiles. The logits never leave the device.

Precision: weights and activations are bfloat16; every contraction
accumulates in float32; norm statistics, softmax, the blend buffer and the
count map are float32.

Departures from the published description: none in the encoder's widths,
depths, heads, patch or window. The decoder's channel plan and skips, the
sliding-window overlap, batch and blending, the intensity normalisation and
the weight draws are assumed (the configuration's ``assumed``); the weights
are random draws from ``weight_seed``, not the bundle's trained ones.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from . import trace

BF16, F32 = jnp.bfloat16, jnp.float32
CONV_DIMS = ("NDHWC", "DHWIO", "NDHWC")
NORM_EPS = 1e-6          # guards the volume's std in the normalisation
LANES = 128              # the TPU's vector lanes: a float32 tile's minor side


@dataclasses.dataclass(frozen=True)
class UNesTConfig:
    classes: int = 133
    patch: int = 4
    embed_dims: Tuple[int, ...] = (128, 256, 512)
    depths: Tuple[int, ...] = (2, 2, 8)
    num_heads: Tuple[int, ...] = (4, 8, 16)
    mlp_ratio: int = 4
    feature_size: int = 32
    roi: int = 96
    overlap: float = 0.7
    sw_batch: int = 4
    ln_eps: float = 1e-6
    in_eps: float = 1e-5
    leaky_slope: float = 0.01

    def __post_init__(self):
        levels = len(self.embed_dims)
        if levels != 3 or len(self.depths) != 3 or len(self.num_heads) != 3:
            raise ValueError("UNesT has three encoder levels")
        grid = self.roi // self.patch
        if self.roi % self.patch or grid % 2 ** (levels - 1):
            raise ValueError(f"roi {self.roi} does not split into patches "
                             f"of {self.patch} and {2 ** (levels - 1)} "
                             f"blocks an axis")
        if self.roi % 32:
            raise ValueError(f"roi {self.roi}: the decoder halves it five "
                             f"times")
        for d, h in zip(self.embed_dims, self.num_heads):
            if d % h:
                raise ValueError(f"width {d} does not split into {h} heads")

    @property
    def block_side(self) -> int:
        """Tokens along each axis of a block (the same at every level)."""
        return self.roi // self.patch // 2 ** (len(self.embed_dims) - 1)


# -- weights ------------------------------------------------------------------

def param_shapes(cfg: UNesTConfig) -> List[Tuple[str, tuple, str]]:
    """Every parameter as (name, shape, draw). Draws: ``linear`` truncated
    normal of std 0.02 (linear layers and positional embeddings), ``conv``
    He-normal with fan-in the inputs each output sums, ``zeros``,
    ``ones``."""
    P, F, C = cfg.patch, cfg.feature_size, cfg.classes
    D = cfg.embed_dims
    out = [("embed.w", (P, P, P, 1, D[0]), "conv"),
           ("embed.b", (D[0],), "zeros")]
    side = cfg.block_side
    for lvl, (d, depth) in enumerate(zip(D, cfg.depths)):
        if lvl:
            out += [(f"agg{lvl}.w", (3, 3, 3, D[lvl - 1], d), "conv"),
                    (f"agg{lvl}.b", (d,), "zeros"),
                    (f"agg{lvl}.ln.g", (d,), "ones"),
                    (f"agg{lvl}.ln.b", (d,), "zeros")]
        blocks = 8 ** (len(D) - 1 - lvl)
        out.append((f"l{lvl}.pos", (blocks, side ** 3, d), "linear"))
        hid = cfg.mlp_ratio * d
        for i in range(depth):
            p = f"l{lvl}.{i}."
            out += [(p + "ln1.g", (d,), "ones"), (p + "ln1.b", (d,), "zeros"),
                    (p + "qkv", (3, d, d), "linear"),
                    (p + "proj", (d, d), "linear"),
                    (p + "proj.b", (d,), "zeros"),
                    (p + "ln2.g", (d,), "ones"), (p + "ln2.b", (d,), "zeros"),
                    (p + "fc1", (d, hid), "linear"),
                    (p + "fc1.b", (hid,), "zeros"),
                    (p + "fc2", (hid, d), "linear"),
                    (p + "fc2.b", (d,), "zeros")]
    out += [("norm.g", (D[-1],), "ones"), ("norm.b", (D[-1],), "zeros")]

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
    for name, cout, skip in _up_plan(cfg):
        out += [(f"{name}.up", (cin, 2, 2, 2, cout), "conv")]
        out += res(name, cout + skip, cout)
        cin = cout
    out += [("head.w", (F, C), "conv"), ("head.b", (C,), "zeros")]
    return out


def _up_plan(cfg: UNesTConfig) -> List[Tuple[str, int, int]]:
    """The decoder's up blocks as (name, output channels, skip channels),
    from R/32 up to R."""
    F, D = cfg.feature_size, cfg.embed_dims
    return [("up5", 16 * F, D[2]), ("up4", 8 * F, 4 * F),
            ("up3", 4 * F, 2 * F), ("up2", 2 * F, 2 * F), ("up1", F, F)]


def _fan_in(name: str, shape: tuple) -> int:
    if name.endswith(".up"):            # transposed: each output sums Cin
        return shape[0]
    return int(np.prod(shape[:-1]))


DRAWS = ("conv", "linear")


def init_weights(cfg: UNesTConfig, seed: int, dtype=BF16) -> Dict[str,
                                                                  jax.Array]:
    """Draw every parameter from ``seed``. The ``conv`` parameters, in the
    order of :func:`param_shapes`, are consecutive slices of one standard
    normal draw of key ``fold_in(PRNGKey(seed), 0)``, each scaled by
    sqrt(2 / fan-in); the ``linear`` ones slices of one normal draw
    truncated at ±2 of key ``fold_in(PRNGKey(seed), 1)``, scaled by 0.02.
    Drawn in float32, then cast."""
    shapes = param_shapes(cfg)
    root = jax.random.PRNGKey(seed)
    flat, used = {}, {}
    for i, draw in enumerate(DRAWS):
        n = sum(int(np.prod(s)) for _, s, d in shapes if d == draw)
        key = jax.random.fold_in(root, i)
        flat[draw] = jax.random.normal(key, (n,), F32) if draw == "conv" \
            else 0.02 * jax.random.truncated_normal(key, -2.0, 2.0, (n,), F32)
        used[draw] = 0
    out = {}
    for name, shape, draw in shapes:
        if draw in DRAWS:
            n = int(np.prod(shape))
            w = flat[draw][used[draw]:used[draw] + n].reshape(shape)
            used[draw] += n
            if draw == "conv":
                w = w * math.sqrt(2.0 / _fan_in(name, shape))
        else:
            w = (jnp.ones if draw == "ones" else jnp.zeros)(shape, F32)
        out[name] = w.astype(dtype)
    return out


# one program draws them all (eager draws compile once per shape)
_init_weights = jax.jit(init_weights, static_argnums=(0, 2))
_weights_lock = threading.Lock()


@functools.lru_cache(maxsize=4)
def _built_weights(cfg: UNesTConfig, seed: int) -> Dict[str, jax.Array]:
    shapes = param_shapes(cfg)
    n = sum(int(np.prod(s)) for _, s, _ in shapes)
    with trace.span("unest.weights", params=n, bytes=2 * n):
        return jax.block_until_ready(_init_weights(cfg, seed, BF16))


def weights(cfg: UNesTConfig, seed: int) -> Dict[str, jax.Array]:
    """The bfloat16 weights on the device, built once per process,
    configuration and seed."""
    with _weights_lock:
        return _built_weights(cfg, seed)


# -- layers -------------------------------------------------------------------

def _conv(x, w, stride: int = 1):
    pad = (w.shape[0] - 1) // 2
    return lax.conv_general_dilated(
        x.astype(BF16), w.astype(BF16), (stride,) * 3, [(pad, pad)] * 3,
        dimension_numbers=CONV_DIMS, preferred_element_type=F32)


def _layer_norm(x, g, b, eps):
    x = x.astype(F32)
    m = x.mean(-1, keepdims=True)
    v = jnp.square(x - m).mean(-1, keepdims=True)
    return (x - m) * lax.rsqrt(v + eps) * g.astype(F32) + b.astype(F32)


def _instance_norm(x, eps):
    m = x.mean((1, 2, 3), keepdims=True)
    v = jnp.square(x - m).mean((1, 2, 3), keepdims=True)
    return (x - m) * lax.rsqrt(v + eps)


def _leaky(x, slope):
    return jnp.where(x >= 0, x, slope * x)


def _res(p, name, x, cfg):
    h = _leaky(_instance_norm(_conv(x, p[f"{name}.conv1"]), cfg.in_eps),
               cfg.leaky_slope)
    h = _instance_norm(_conv(h, p[f"{name}.conv2"]), cfg.in_eps)
    skip = p.get(f"{name}.conv3")
    r = x.astype(F32) if skip is None else \
        _instance_norm(_conv(x, skip), cfg.in_eps)
    return _leaky(h + r, cfg.leaky_slope).astype(BF16)


def _up(x, w):
    """Transposed conv, kernel 2, stride 2: (B,X,Y,Z,Cin) -> (B,2X,2Y,2Z,Co)."""
    B, X, Y, Z, _ = x.shape
    y = jnp.einsum("bxyzc,cijko->bxiyjzko", x.astype(BF16), w.astype(BF16),
                   preferred_element_type=F32)
    return y.reshape(B, 2 * X, 2 * Y, 2 * Z, w.shape[-1]).astype(BF16)


def _embed(p, x, P):
    """Patch embedding as a matmul over each P³ patch (the P-stride conv)."""
    B, R = x.shape[0], x.shape[1]
    g = R // P
    t = x.reshape(B, g, P, g, P, g, P).transpose(0, 1, 3, 5, 2, 4, 6)
    w = p["embed.w"].reshape(P ** 3, -1)
    y = jnp.einsum("bxyzk,kd->bxyzd", t.reshape(B, g, g, g, P ** 3)
                   .astype(BF16), w.astype(BF16), preferred_element_type=F32)
    return (y + p["embed.b"].astype(F32)).astype(BF16)


def blockify(x, n: int):
    """(B, G, G, G, D) -> (B, n³, (G/n)³, D): n blocks an axis, blocks and
    the tokens inside each in row-major order."""
    B, G, _, _, D = x.shape
    s = G // n
    x = x.reshape(B, n, s, n, s, n, s, D).transpose(0, 1, 3, 5, 2, 4, 6, 7)
    return x.reshape(B, n ** 3, s ** 3, D)


def deblockify(x, n: int):
    """The inverse of :func:`blockify`."""
    B, _, T, D = x.shape
    s = round(T ** (1 / 3))
    x = x.reshape(B, n, n, n, s, s, s, D).transpose(0, 1, 4, 2, 5, 3, 6, 7)
    return x.reshape(B, n * s, n * s, n * s, D)


def aggregate(p, lvl: int, x, eps: float):
    """Block aggregation into level ``lvl``: conv 3³ with bias, LayerNorm
    over channels, max-pool 3³ of stride 2 (padding 1)."""
    h = _conv(x, p[f"agg{lvl}.w"]) + p[f"agg{lvl}.b"].astype(F32)
    h = _layer_norm(h, p[f"agg{lvl}.ln.g"], p[f"agg{lvl}.ln.b"],
                    eps).astype(BF16)
    return lax.reduce_window(h, jnp.array(-jnp.inf, BF16), lax.max,
                             (1, 3, 3, 3, 1), (1, 2, 2, 2, 1),
                             [(0, 0), (1, 1), (1, 1), (1, 1), (0, 0)])


def _mm(spec, a, b):
    return jnp.einsum(spec, a.astype(BF16), b.astype(BF16),
                      preferred_element_type=F32)


def _transformer_layer(p, pre, z, heads, eps):
    B, N, T, D = z.shape
    dh = D // heads
    h = _layer_norm(z, p[pre + "ln1.g"], p[pre + "ln1.b"], eps)
    qkv = _mm("bntd,sde->sbnte", h, p[pre + "qkv"]).astype(BF16)
    q, k, v = (t.reshape(B, N, T, heads, dh) for t in qkv)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", q, k,
                   preferred_element_type=F32) / math.sqrt(dh)
    a = jax.nn.softmax(s, axis=-1).astype(BF16)
    o = jnp.einsum("bnhqk,bnkhd->bnqhd", a, v, preferred_element_type=F32)
    o = _mm("bnte,ed->bntd", o.reshape(B, N, T, D), p[pre + "proj"])
    z = (z.astype(F32) + o + p[pre + "proj.b"].astype(F32)).astype(BF16)
    h = _layer_norm(z, p[pre + "ln2.g"], p[pre + "ln2.b"], eps)
    h = _mm("bntd,df->bntf", h, p[pre + "fc1"]) + p[pre + "fc1.b"].astype(F32)
    h = jax.nn.gelu(h, approximate=False)
    h = _mm("bntf,fd->bntd", h, p[pre + "fc2"]) + p[pre + "fc2.b"].astype(F32)
    return (z.astype(F32) + h).astype(BF16)


def encode(p, x, cfg: UNesTConfig):
    """The patch-embedding state and each level's output (the last one
    normed), for B windows ``x`` of shape (B, R, R, R, 1)."""
    L = len(cfg.embed_dims)
    t = _embed(p, x, cfg.patch)
    h, levels = t, []
    for lvl in range(L):
        if lvl:
            h = aggregate(p, lvl, h, cfg.ln_eps)
        n = 2 ** (L - 1 - lvl)
        z = blockify(h, n) + p[f"l{lvl}.pos"]
        for i in range(cfg.depths[lvl]):
            z = _transformer_layer(p, f"l{lvl}.{i}.", z, cfg.num_heads[lvl],
                                   cfg.ln_eps)
        h = deblockify(z, n)
        levels.append(h)
    levels[-1] = _layer_norm(h, p["norm.g"], p["norm.b"],
                             cfg.ln_eps).astype(BF16)
    return t, levels


def features(p, windows, cfg: UNesTConfig):
    """The decoder's last features (B, R, R, R, F) of B normalised windows
    (B, R, R, R)."""
    x = windows[..., None].astype(BF16)
    t, (h0, h1, h2) = encode(p, x, cfg)
    e0 = _res(p, "enc0", x, cfg)
    e1 = _res(p, "enc1", _up(t, p["enc1.up"]), cfg)
    e2 = _res(p, "enc2", h0, cfg)
    e3 = _res(p, "enc3", h1, cfg)
    d = _conv(h2, p["dec4"], stride=2).astype(BF16)
    for (name, _, _), skip in zip(_up_plan(cfg), (h2, e3, e2, e1, e0)):
        d = _res(p, name, jnp.concatenate([_up(d, p[f"{name}.up"]), skip],
                                          -1), cfg)
    return d


def head(p, d):
    """The 1³ conv onto the classes: logits (..., C), float32."""
    return _mm("...f,fc->...c", d, p["head.w"]) + p["head.b"].astype(F32)


def forward(p, windows, cfg: UNesTConfig):
    """Logits (B, R, R, R, C), float32, of B normalised windows (B, R, R, R)."""
    return head(p, features(p, windows, cfg))


# -- sliding window -----------------------------------------------------------

def padding(shape: Sequence[int], roi: int) -> List[Tuple[int, int]]:
    """Zeros before and after each axis that is shorter than ``roi``: half
    before, the rest after."""
    out = []
    for s in shape:
        diff = max(roi - int(s), 0)
        out.append((diff // 2, diff - diff // 2))
    return out


def window_starts(shape: Sequence[int], roi: int, overlap: float
                  ) -> np.ndarray:
    """Window starts (n, 3) over a volume at least ``roi`` on each axis:
    every ⌊roi (1 - overlap)⌋ voxels until a window reaches the end, the
    last one clipped to ``size - roi``, duplicates dropped."""
    per_axis = []
    for s in (int(v) for v in shape):
        if s < roi:
            raise ValueError(f"axis of {s} is shorter than the window {roi}")
        step = roi if s == roi else max(int(roi * (1 - overlap)), 1)
        starts = []
        for i in range(-(-s // step)):
            starts.append(min(i * step, s - roi))
            if i * step + roi >= s:
                break
        per_axis.append(sorted(set(starts)))
    grid = np.meshgrid(*per_axis, indexing="ij")
    return np.stack([g.reshape(-1) for g in grid], -1).astype(np.int32)


def padded_shape(shape: Sequence[int], roi: int) -> Tuple[int, ...]:
    return tuple(int(s) + a + b for s, (a, b) in zip(shape,
                                                     padding(shape, roi)))


def n_windows(shape: Sequence[int], cfg: UNesTConfig) -> int:
    return len(window_starts(padded_shape(shape, cfg.roi), cfg.roi,
                             cfg.overlap))


def blend_order(shape: Sequence[int]) -> Tuple[int, ...]:
    """The spatial axes in a class-major blend buffer's order: those whose
    size is a multiple of 128 last, so that the TPU's (8, 128) tiles of the
    buffer need no padding (a (5, 256, 256, 176) buffer would pad 176 to
    256)."""
    return tuple(sorted(range(3), key=lambda a: shape[a] % 128 == 0))


def blend_parts(classes: int, shape: Sequence[int]
                ) -> List[Tuple[int, int, Tuple[int, ...]]]:
    """The blend buffers as (first class, end class, the axes of a window's
    (x, y, z, class) logits in the buffer's order). The first
    ``LANES`` × ⌊C / ``LANES``⌋ classes lie minor, on the TPU's 128 lanes,
    so that a window adds whole rows of lanes; the rest class-major, in
    :func:`blend_order`. With all 133 classes class-major, a window adds
    133 · 96² rows of 96 floats at offsets off the lanes' tiles: 13.6 ms a
    96³ window on a TPU v5e, 18.6% of the stage's device time."""
    lanes = LANES * (classes // LANES)
    parts = [(0, lanes, (0, 1, 2, 3))] if lanes else []
    if classes > lanes:
        parts.append((lanes, classes, (3,) + blend_order(shape)))
    return parts


def _row_major(x):
    """Hold ``x`` row-major. XLA would otherwise lay a blend buffer out as
    the windows' logits come (a window axis minor, the classes beside it),
    padding its tiles by half, or copy the whole buffer each window to add
    a window in the logits' layout."""
    return with_layout_constraint(x, Layout(tuple(range(x.ndim))))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _sliding_window(vol, params, *, cfg: UNesTConfig):
    """The whole inference as one program per volume shape: the volume and
    the weights are arguments, never constants of the program."""
    R, C = cfg.roi, cfg.classes
    pads = padding(vol.shape, R)
    v = (vol - vol.mean()) / (vol.std() + NORM_EPS)
    v = jnp.pad(v, pads)
    parts = blend_parts(C, v.shape)
    starts = window_starts(v.shape, R, cfg.overlap)
    n = len(starts)
    B = min(cfg.sw_batch, n)
    batches = -(-n // B)
    fill = batches * B - n              # the last batch's empty places
    weight = np.concatenate([np.ones(n), np.zeros(fill)]).astype(np.float32)
    starts = np.concatenate([starts, np.repeat(starts[-1:], fill, 0)])
    starts = jnp.asarray(starts.reshape(batches, B, 3))
    weight = jnp.asarray(weight.reshape(batches, B))

    def body(i, carry):
        at = starts[i]
        wins = jnp.stack([lax.dynamic_slice(v, at[j], (R, R, R))
                          for j in range(B)])
        d = features(params, wins, cfg)

        def add(j, carry):              # one window's logits at a time
            bufs, cnt = carry
            w, a = weight[i, j], at[j]
            logits = w * head(params, d[j])                 # (x, y, z, C)
            new = []
            for (lo, hi, axes), buf in zip(parts, bufs):
                upd = _row_major(jnp.transpose(logits[..., lo:hi], axes))
                pos = tuple(a[k] if k < 3 else 0 for k in axes)
                cur = lax.dynamic_slice(buf, pos, upd.shape)
                new.append(_row_major(lax.dynamic_update_slice(
                    buf, cur + upd, pos)))
            cur = lax.dynamic_slice(cnt, a, (R, R, R))
            return tuple(new), lax.dynamic_update_slice(cnt, cur + w, a)
        return lax.fori_loop(0, B, add, carry)

    bufs = tuple(_row_major(jnp.zeros(
        tuple(hi - lo if k == 3 else v.shape[k] for k in axes), F32))
        for lo, hi, axes in parts)
    cnt = jnp.zeros(v.shape, F32)
    bufs, cnt = lax.fori_loop(0, batches, body, (bufs, cnt))
    crop = tuple(slice(a, a + s) for (a, _), s in zip(pads, vol.shape))
    cnt = cnt[crop]
    avgs = []                           # (average, class axis, spatial axes)
    for (lo, hi, axes), buf in zip(parts, bufs):
        c, sp = axes.index(3), [k for k in axes if k < 3]
        box = tuple(slice(None) if k == 3 else crop[k] for k in axes)
        avgs.append((buf[box] / jnp.expand_dims(jnp.transpose(cnt, sp), c),
                     c, sp))

    def natural(x, sp):                 # buffer order -> (x, y, z)
        return jnp.transpose(x, [sp.index(k) for k in range(3)])

    top = label = None
    for (lo, _, _), (avg, c, sp) in zip(parts, avgs):
        t, arg = natural(avg.max(c), sp), natural(avg.argmax(c), sp) + lo
        if top is None:
            top, label = t, arg
        else:                           # ties keep the lower class
            label = jnp.where(t > top, arg, label)
            top = jnp.maximum(top, t)
    total = sum(natural(jnp.exp(avg - jnp.expand_dims(
        jnp.transpose(top, sp), c)).sum(c), sp) for avg, c, sp in avgs)
    return {"segmentation": label.astype(jnp.uint8),
            "label_confidence": 1.0 / total}


def segment(vol, cfg: UNesTConfig, seed: int) -> Dict[str, jax.Array]:
    """Segment one volume (X, Y, Z): label and confidence on its grid, on
    the device and ready."""
    params = weights(cfg, seed)
    n = n_windows(vol.shape, cfg)
    buffer_bytes = 4 * cfg.classes * math.prod(padded_shape(vol.shape,
                                                            cfg.roi))
    with trace.span("unest.infer", windows=n, roi=cfg.roi,
                    classes=cfg.classes, bytes=buffer_bytes):
        out = jax.block_until_ready(
            _sliding_window(jnp.asarray(vol, F32), params, cfg=cfg))
    trace.count("unest.windows", n)
    return out
