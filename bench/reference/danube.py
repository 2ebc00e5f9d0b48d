"""Plain reference of the H2O-Danube decoder, in float32 jax.numpy.

Every layer is the same attention layer, global or sliding-window:
pre-RMSNorm, GQA attention with rotary embeddings (split halves), a
SiLU-gated FFN; the output head is the input embedding (tied) or a matrix
of its own (untied). The weights are drawn from the seed exactly
as the served model draws them (same keys, same order, a normal draw over
the square root of the fan-in, rounded to the served type), layer by layer, so
the reference never holds the whole model and takes nothing the program
made. Norm scales start at zero offset (the norm multiplies by ``1 + w``
with ``w = 0``), so they are left out.

All matrix products run at ``Precision.HIGHEST``. Sequences are processed
one at a time inside each layer (``lax.map``), so an attention score
matrix of one sequence is the largest temporary.

The comparison: the program served greedy tokens. At each served token's
position the reference gives its logits over the whole context (prompt,
then the served tokens before it); the gap is how far the served token's
logit lies below the reference's best. ``control`` does the same with the
reference's argmax computed in float8 (e4m3) weights and activations, the
step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# -- weights, drawn from the seed ---------------------------------------------

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _dense(key, shape, dtype):
    fan_in = shape[0] if len(shape) >= 2 else 1
    return (1.0 * jax.random.normal(key, shape, jnp.float32)
            / np.sqrt(fan_in)).astype(DTYPES[dtype])


def _vocab_rows(vocab: int) -> int:
    return -(-vocab // 256) * 256


def layer_keys(seed: int, n_layers: int):
    """(embedding key, output head key, one key per layer), in the served
    model's order."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return ks[0], ks[2], jax.random.split(ks[3], n_layers)


@functools.partial(jax.jit, static_argnums=(1, 2))
def dense(key, shape: Tuple[int, int], dtype: str):
    return _dense(key, shape, dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def layer_weights(stage_key, dims: Tuple[int, int, int, int, int], dtype: str):
    d, h, kv, hd, ff = dims
    key = jax.random.split(stage_key, 1)[0]
    k_attn, k_ffn, _ = jax.random.split(key, 3)
    a = jax.random.split(k_attn, 4)
    f = jax.random.split(k_ffn, 3)
    shapes = {"wq": (a[0], (d, h, hd)), "wk": (a[1], (d, kv, hd)),
              "wv": (a[2], (d, kv, hd)), "wo": (a[3], (h * hd, d)),
              "w_gate": (f[0], (d, ff)), "w_up": (f[1], (d, ff)),
              "w_down": (f[2], (ff, d))}
    return {name: _dense(k, shape, dtype) for name, (k, shape) in shapes.items()}


# -- precision ------------------------------------------------------------------

def _fp8(a, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(FP8).astype(jnp.float32) * scale


def _weights_f32(w, fp8: bool):
    out = {}
    for name, v in w.items():
        v = v.astype(jnp.float32)
        out[name] = _fp8(v, 0) if fp8 else v
    return out


def _act(x, fp8: bool):
    return _fp8(x, -1) if fp8 else x


# -- the forward ------------------------------------------------------------------

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, x.shape[-1], 2, dtype=jnp.float32)
                             / x.shape[-1]))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _one_sequence(x, w, arch, fp8):
    """One layer over one sequence ``x`` [L, d]."""
    eps, theta, window = arch["norm_eps"], arch["rope_theta"], arch["window"]
    L = x.shape[0]
    pos = jnp.arange(L)
    h = _act(_rms(x, eps), fp8)
    q = jnp.einsum("ld,dhk->lhk", h, w["wq"], precision=HIGHEST)
    k = jnp.einsum("ld,dhk->lhk", h, w["wk"], precision=HIGHEST)
    v = jnp.einsum("ld,dhk->lhk", h, w["wv"], precision=HIGHEST)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    n_heads, n_kv, hd = q.shape[1], k.shape[1], q.shape[2]
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    rows, cols = pos[:, None], pos[None, :]
    mask = cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(L, -1)
    x = x + jnp.einsum("lk,kd->ld", _act(o, fp8), w["wo"], precision=HIGHEST)
    h = _act(_rms(x, eps), fp8)
    g = jax.nn.silu(jnp.einsum("ld,df->lf", h, w["w_gate"], precision=HIGHEST))
    u = jnp.einsum("ld,df->lf", h, w["w_up"], precision=HIGHEST)
    return x + jnp.einsum("lf,fd->ld", _act(g * u, fp8), w["w_down"],
                          precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w_served, arch_items, fp8):
    arch = dict(arch_items)
    w = _weights_f32(w_served, fp8)
    return jax.lax.map(lambda xs: _one_sequence(xs, w, arch, fp8), x)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _served(x, head_served, seq_idx, pos_idx, eps, vocab, fp8):
    """Logits at the served positions: [M, vocab]. ``head_served`` is
    [d, vocab rows]."""
    w = head_served.astype(jnp.float32)
    if fp8:
        w = _fp8(w, 0)
    h = _act(_rms(x[seq_idx, pos_idx], eps), fp8)
    return jnp.einsum("md,dv->mv", h, w, precision=HIGHEST)[:, :vocab]


def _arch(model: Dict[str, Any]) -> Dict[str, Any]:
    unit = tuple(model["pattern_unit"])
    if unit not in (("attn_global",), ("attn_local",)):
        raise ValueError("this reference covers decoders whose every layer "
                         f"is one attention kind, not {unit}")
    if model.get("embed_scale"):
        raise ValueError("this reference covers unscaled embeddings")
    return {"norm_eps": float(model["norm_eps"]),
            "rope_theta": float(model["rope_theta"]),
            "window": model["window"] if unit == ("attn_local",) else None}


def forward_served(model: Dict[str, Any], seed: int,
                   samples: Sequence[Tuple[np.ndarray, np.ndarray]],
                   length: int, n_seqs: int, precisions=("f32",)):
    """Run the reference over ``samples`` (prompt, served tokens) and
    return, per precision, the logits at each served position, stacked
    over all samples: {precision: [M, vocab] device array}.

    Sequences are padded to ``length`` and their number to ``n_seqs``, so
    that every run of a cell compiles the same programs; padding sits after
    each sequence's last token and causal attention keeps it out."""
    if len(samples) > n_seqs:
        raise ValueError(f"{len(samples)} samples, room for {n_seqs}")
    arch = tuple(sorted(_arch(model).items()))
    d, vocab = model["d_model"], model["vocab"]
    dims = (d, model["n_heads"], model["n_kv_heads"], model["head_dim"],
            model["d_ff"])
    tokens = np.zeros((n_seqs, length), np.int32)
    seq_idx, pos_idx = [], []
    for i, (prompt, served) in enumerate(samples):
        # the model reads the prompt and every served token but the last;
        # position P - 1 + j predicts served token j
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        if len(seq) > length:
            raise ValueError(f"sequence of {len(seq)} over {length}")
        tokens[i, :len(seq)] = seq
        first = len(prompt) - 1
        seq_idx += [i] * len(served)
        pos_idx += list(range(first, first + len(served)))
    seq_idx = jnp.asarray(seq_idx, jnp.int32)
    pos_idx = jnp.asarray(pos_idx, jnp.int32)

    embed_key, head_key, stage_keys = layer_keys(seed, model["n_layers"])
    dtype = model["dtype"]
    embed = dense(embed_key, (_vocab_rows(vocab), d), dtype)
    x0 = embed[jnp.asarray(tokens)].astype(jnp.float32)
    if model["tied_embeddings"]:
        head = embed.T
    else:
        del embed
        head = dense(head_key, (d, _vocab_rows(vocab)), dtype)
    xs = {p: x0 for p in precisions}
    for i in range(model["n_layers"]):
        w = layer_weights(stage_keys[i], dims, dtype)
        for p in precisions:
            xs[p] = _layer(xs[p], w, arch, p == "fp8")
        del w
    return {p: _served(xs[p], head, seq_idx, pos_idx, float(model["norm_eps"]),
                       vocab, p == "fp8")
            for p in precisions}


def _gaps(ref_logits, tokens):
    best = jnp.max(ref_logits, axis=-1)
    return best - jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]


def compare(model: Dict[str, Any], seed: int,
            samples: Sequence[Tuple[np.ndarray, np.ndarray]], length: int,
            n_seqs: int, control: bool = False) -> Dict[str, float]:
    """``max_logit_gap``: the widest gap of a served token below the
    reference's best logit. With ``control``, also
    ``control_max_logit_gap``: the same for the tokens that float8 weights
    and activations put first."""
    precisions = ("f32", "fp8") if control else ("f32",)
    logits = forward_served(model, seed, samples, length, n_seqs, precisions)
    served = jnp.asarray(np.concatenate([s for _, s in samples]), jnp.int32)
    ref = logits["f32"]
    out = {"max_logit_gap": float(jnp.max(_gaps(ref, served)))}
    if control:
        ctl = jnp.argmax(logits["fp8"], axis=-1)
        out["control_max_logit_gap"] = float(jnp.max(_gaps(ref, ctl)))
    return out
