"""Plain float32 reference of a dense GQA decoder whose projections are
served as SplitQuantV2 INT4 weights.

Independent of the program: it makes the weights itself from the seed (the
published layer recipe: normal draws in the stored dtype, scaled by the
inverse square root of the fan-in, norm weights one), splits every
projection into three k-means clusters and quantizes each to 4 bits over
its value range extended to zero (arXiv:2503.07657, section 3), and runs
the forward pass in ``jax.numpy`` at float32 with ``highest`` matmul
precision: RMSNorm, RoPE (rotate-half), optional per-head q/k RMSNorm,
causal softmax attention with grouped KV heads, SiLU-gated MLP.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale before the product, the step below the
bf16 compute the configuration states.

The weights are made and quantized one layer at a time, and every sampled
sequence passes through a layer before the next is made, so the reference
never holds more than one layer in float32.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

BINS = 4096
ITERS = 32
CLUSTERS = 3
PAD = 512          # padded lengths are multiples of this (causal masking
#                    makes the pad inert)
F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class RefConfig:
    """The sizes the reference reads from a configuration's ``model``."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    tie_embeddings: bool
    qk_norm: bool
    rope_theta: float
    norm_eps: float
    dtype: str

    @classmethod
    def from_model(cls, model: dict) -> "RefConfig":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})


# -- weights ------------------------------------------------------------------

def _normal(key, shape, dtype, fan_in):
    return jax.random.normal(key, shape, dtype) * fan_in ** -0.5


def top_keys(seed_key):
    """(embed, layers, extra, head) keys, in the order the layer recipe
    draws them."""
    k_embed, k_layers, k_extra, k_head, _ = jax.random.split(seed_key, 5)
    return k_embed, k_layers, k_extra, k_head


@functools.partial(jax.jit, static_argnames=("cfg",))
def _layer_weights(key, cfg):
    d, h, kv, hd, ff = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, \
        cfg.head_dim, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    k_attn, k_mlp, _, _ = jax.random.split(key, 4)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    return {
        "wq": _normal(ka[0], (d, h * hd), dt, d),
        "wk": _normal(ka[1], (d, kv * hd), dt, d),
        "wv": _normal(ka[2], (d, kv * hd), dt, d),
        "wo": _normal(ka[3], (h * hd, d), dt, h * hd),
        "w_up": _normal(km[0], (d, ff), dt, d),
        "w_down": _normal(km[1], (ff, d), dt, ff),
        "w_gate": _normal(km[2], (d, ff), dt, d),
    }


# -- SplitQuantV2 at INT4 -----------------------------------------------------

CHUNK = 1 << 18    # values per step of the on-device value count
COLS = 4096        # columns per call of it: one call over a 0.5-billion
#                    -value head took minutes to compile for a v5e


@jax.jit
def _bf16_value_counts(w):
    """How often each of the 65536 bf16 bit patterns occurs in ``w``:
    a (256 x 256) histogram of the high and low bytes, one one-hot matmul
    per chunk (exact: 0/1 products, float32 sums far below 2**24)."""
    bits = jax.lax.bitcast_convert_type(w.reshape(-1), jnp.uint16)
    bits = bits.astype(jnp.int32)
    bits = jnp.pad(bits, (0, (-bits.size) % CHUNK), constant_values=-1)

    def step(acc, c):
        hi = jax.nn.one_hot(c >> 8, 256, dtype=jnp.bfloat16)
        lo = jax.nn.one_hot(c & 255, 256, dtype=jnp.bfloat16)
        return acc + jnp.dot(hi.T, lo, preferred_element_type=jnp.float32), None

    acc, _ = jax.lax.scan(step, jnp.zeros((256, 256), jnp.float32),
                          bits.reshape(-1, CHUNK))
    return acc.reshape(-1)


def kmeans_bounds(w) -> np.ndarray:
    """The two thresholds between three 1-D k-means clusters of ``w``:
    Lloyd's algorithm over a ``BINS``-bin histogram of the values, started
    from the 1/6, 1/2 and 5/6 quantiles (linear interpolation at a float32
    index), ``ITERS`` steps, all in float32.

    ``w`` is stored in bf16, so it holds at most 65536 distinct values: the
    chip counts them, and the quantiles (exact order statistics) and the
    histogram follow on the host from those counts, with no sort of the
    tensor."""
    if w.dtype != jnp.bfloat16:
        raise TypeError(f"the reference quantizes bf16 weights, got {w.dtype}")
    counts = sum(np.asarray(_bf16_value_counts(w[:, i:i + COLS]))
                 for i in range(0, w.shape[1], COLS)).astype(np.int64)
    keys = np.nonzero(counts)[0].astype(np.uint16)
    vals = keys.view(jnp.bfloat16).astype(np.float32)
    order = np.argsort(vals, kind="stable")
    vals, cnt = vals[order], counts[keys][order]
    cum = np.cumsum(cnt)

    def kth(i: int) -> np.float32:      # the i-th smallest value, from 0
        return vals[np.searchsorted(cum, i, side="right")]

    n = np.float32(cum[-1])
    q = (np.arange(CLUSTERS, dtype=np.float32) + np.float32(0.5)) \
        / np.float32(CLUSTERS) * (n - np.float32(1))
    low, high = np.floor(q), np.ceil(q)
    hw = q - low
    cents = np.array([kth(int(a)) * (np.float32(1) - h) + kth(int(b)) * h
                      for a, b, h in zip(low, high, hw)], np.float32)
    lo, hi = vals[0], vals[-1]
    span = np.maximum(hi - lo, np.float32(1e-12))
    bin_of = np.clip(((vals - lo) / span * np.float32(BINS)).astype(np.int32),
                     0, BINS - 1)
    hist = np.bincount(bin_of, weights=cnt, minlength=BINS)
    centers = lo + (np.arange(BINS, dtype=np.float32) + np.float32(0.5)) \
        * (span / np.float32(BINS))
    for _ in range(ITERS):
        near = np.argmin(np.abs(centers[:, None] - cents[None, :]), axis=1)
        mass = np.bincount(near, weights=hist, minlength=CLUSTERS)
        tot = np.bincount(near, weights=hist * centers, minlength=CLUSTERS)
        cents = np.where(mass > 0, tot / np.maximum(mass, 1.0),
                         cents).astype(np.float32)
        cents = np.sort(cents)
    return (cents[:-1] + cents[1:]) / np.float32(2.0)


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize_split(w, bounds, bits: int = 4):
    x = w.astype(jnp.float32)
    ids = jnp.sum(x[..., None] > bounds, axis=-1)
    qmin, qmax = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    out = jnp.zeros_like(x)
    for c in range(CLUSTERS):
        sel = ids == c
        beta = jnp.minimum(jnp.min(jnp.where(sel, x, jnp.inf)), 0.0)
        alpha = jnp.maximum(jnp.max(jnp.where(sel, x, -jnp.inf)), 0.0)
        beta = jnp.where(jnp.any(sel), beta, 0.0)
        alpha = jnp.where(jnp.any(sel), alpha, 0.0)
        s = (2.0 ** bits - 1.0) / jnp.maximum(alpha - beta, 1e-12)
        z = -(2.0 ** (bits - 1)) - jnp.round(s * beta)
        q = jnp.clip(jnp.round(s * x) + z, qmin, qmax)
        out = jnp.where(sel, (q - z) / s, out)
    return out


def split_quantize(w, bits: int = 4):
    """Dequantized float32 weight: three k-means clusters, each quantized
    per tensor over hull(cluster range, 0)."""
    return _quantize_split(w, jnp.asarray(kmeans_bounds(w)), bits=bits)


# -- forward ------------------------------------------------------------------

def _f8(t):
    """Round to float8 e4m3 under a per-tensor scale, back to float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
    s = F8_MAX / amax
    return (t * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, fp8: bool):
    if fp8:
        a, b = _f8(a), _f8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    s, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def layer_forward(x, w, cfg, fp8: bool = False):
    """One decoder layer over one sequence x (S, D), float32."""
    s = x.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a = _rms(x, cfg.norm_eps)
    q = _mm(a, w["wq"], fp8).reshape(s, h, hd)
    k = _mm(a, w["wk"], fp8).reshape(s, kv, hd)
    v = _mm(a, w["wv"], fp8).reshape(s, kv, hd)
    if cfg.qk_norm:
        q, k = _rms(q, cfg.norm_eps), _rms(k, cfg.norm_eps)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    g = h // kv
    qg = q.reshape(s, kv, g, hd).transpose(1, 2, 0, 3)        # (KV, G, S, hd)
    kt = k.transpose(1, 2, 0)[:, None]                         # (KV, 1, hd, S)
    sc = _mm(qg, kt, fp8) * hd ** -0.5                        # (KV, G, S, S)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _mm(p, v.transpose(1, 0, 2)[:, None], fp8)             # (KV, G, S, hd)
    o = o.transpose(2, 0, 1, 3).reshape(s, h * hd)
    x = x + _mm(o, w["wo"], fp8)
    b = _rms(x, cfg.norm_eps)
    hmid = jax.nn.silu(_mm(b, w["w_gate"], fp8)) * _mm(b, w["w_up"], fp8)
    return x + _mm(hmid, w["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def _head_gaps(x, head_t, served, cfg, fp8: bool = False):
    """Per position: the reference's best logit minus the logit of
    ``served`` (-1 = not compared), and the argmax."""
    logits = _mm(_rms(x, cfg.norm_eps), head_t, fp8)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.maximum(served, 0)[:, None], -1)[:, 0]
    return jnp.where(served >= 0, best - got, 0.0), jnp.argmax(logits, -1)


def gaps(cfg, seed_key, seqs, served, precisions=("f32",), pad_to=0):
    """Run the reference over ``seqs`` (token id arrays, each a prompt and
    its served tokens but the last) and return, per precision and sequence,
    ``(gap, argmax)`` arrays over positions.

    ``served[i][p]`` is the token the program served after position ``p``
    of ``seqs[i]`` (``-1`` where none is compared). For ``"f32"`` the gap is
    how far the served token's logit lies below the reference's best. For
    ``"fp8"`` (the control) the served token is replaced by the fp8
    reference's own first choice, and its gap is read in the f32 logits.

    Every sequence is padded to one length, ``pad_to`` (a cell's longest
    row) or the longest sequence, rounded up to ``PAD``: the forward then
    compiles once for all the runs of a cell.
    """
    n = max(max(len(s) for s in seqs), pad_to)
    with jax.default_matmul_precision("highest"):
        return _gaps(cfg, seed_key, seqs, served, precisions,
                     -(-n // PAD) * PAD)


def _gaps(cfg, seed_key, seqs, served, precisions, padded):
    k_embed, k_layers, _, k_head = top_keys(seed_key)
    dt = jnp.dtype(cfg.dtype)
    table = jax.jit(functools.partial(
        _normal, shape=(cfg.vocab_size, cfg.d_model), dtype=dt,
        fan_in=cfg.d_model))(k_embed)
    lens = [len(s) for s in seqs]
    toks = [jnp.asarray(np.pad(np.asarray(s, np.int32),
                               (0, padded - len(s)))) for s in seqs]
    xs = {p: [table[t].astype(jnp.float32) for t in toks] for p in precisions}
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    for i in range(cfg.n_layers):
        raw = _layer_weights(layer_keys[i], cfg)
        w = {n: split_quantize(t) for n, t in raw.items()}
        del raw
        for p in precisions:
            xs[p] = [layer_forward(x, w, cfg, fp8=(p == "fp8"))
                     for x in xs[p]]
        del w
    if cfg.tie_embeddings:
        head_t = table.astype(jnp.float32).T
    else:
        head = jax.jit(functools.partial(
            _normal, shape=(cfg.d_model, cfg.vocab_size), dtype=dt,
            fan_in=cfg.d_model))(k_head)
        head_t = split_quantize(head)
        del head
    del table
    out = {}
    srv = [jnp.asarray(np.pad(np.asarray(s, np.int32),
                              (0, padded - len(s)), constant_values=-1))
           for s in served]
    f32 = []
    if "f32" in xs:
        for x, s in zip(xs["f32"], srv):
            g, a = _head_gaps(x, head_t, s, cfg)
            f32.append((g, a))
        out["f32"] = [(np.asarray(g)[:n], np.asarray(a)[:n])
                      for (g, a), n in zip(f32, lens)]
    if "fp8" in xs:
        res = []
        for x, xf, s, n in zip(xs["fp8"], xs.get("f32", [None] * len(lens)),
                               srv, lens):
            _, pick = _head_gaps(x, head_t, s, cfg, fp8=True)
            pick = jnp.where(s >= 0, pick, -1)
            g, _ = _head_gaps(xf, head_t, pick, cfg)
            res.append((np.asarray(g)[:n], np.asarray(pick)[:n]))
        out["fp8"] = res
    return out
