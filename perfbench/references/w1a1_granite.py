"""The plain reference of a binarized (w1a1) granite-style decoder, in
straightforward ``jax.numpy``, from BMXNet's description (sign of weights
and activations into {-1, +1} with sign(0) = +1, full-precision first and
last layer) and the Llama/Granite block (pre-RMSNorm, rotary GQA
attention, gated SiLU MLP, tied embedding), with an fp32 KV cache.  It
imports nothing of the program and is fed only the benchmark's own seeded
float32 weights (``weights.py``), regenerated one layer at a time so that
it fits beside nothing.

``dtype=float32`` runs at ``highest`` matmul precision: the reference.
``dtype=bfloat16`` is the control, the same arithmetic one precision
below what the configuration states (float32 compute).

What a serving cell compares (``kinds/serve_offline.py``): teacher-forced
on each prompt and its served tokens, the gap by which each served token's
logit lies below the reference's best at its position; the same for the
token the control puts first; and where a request misses, how the
reference itself answers under a softmax temperature moved by a
rounding-sized step (``divergence``): from which position its hidden
state changes, and by how much its own first choices then miss.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights
from bench import seed_key
from weights import Dims

F32, BF16 = jnp.float32, jnp.bfloat16


def check_config(cfg: dict) -> None:
    """Refuse a configuration that this reference does not model: it
    binarizes every block (no scale) and keeps the KV cache in fp32."""
    prog = cfg["program"]
    if (prog["policy"], prog.get("policy_args") or {}, prog["kv_bits"]) != (
            "binary", {}, None):
        raise ValueError("the w1a1_granite reference models the plain "
                         "binary policy with an fp32 KV cache only")


def precision(dtype):
    return "highest" if dtype == F32 else "default"


def sign(x):
    """{-1, +1} with sign(0) = +1."""
    return jnp.where(x >= 0, 1, -1).astype(x.dtype)


def rmsnorm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1 + scale.astype(x.dtype))


def rope(x, pos, theta):
    """Rotary embedding, rotate-half form.  x (T, H, dh), pos (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def bdense(x, w):
    """Binary GEMM: sign(x) @ sign(w)."""
    return sign(x) @ sign(w.astype(x.dtype))


NEG = -2.0e38  # the score of a masked key


def attention_paged(q, k, v, scale, page: int):
    """Causal GQA over one sequence as the softmax over a paged cache is
    taken: one page of ``page`` keys at a time in position order, with a
    running max, sum and accumulator (the online softmax; mathematically
    the softmax).  Attention of the w1a1 model is near one-hot over
    whole-number values, so an output element is often exactly zero except
    for terms at fp32's underflow threshold, whose sign the order of the
    arithmetic decides; one flipped sign diverges every later position
    (PERF.md, Findings), so the reference keeps the paged order.
    q (T, H, dh), k/v (T, KVH, dh) -> (T, H * dh)."""
    t, h, dh = q.shape
    kvh = k.shape[1]
    g = h // kvh
    n = -(-t // page)
    pad = ((0, n * page - t), (0, 0), (0, 0))
    kp = jnp.pad(k, pad).reshape(n, page, kvh, dh)
    vp = jnp.pad(v, pad).reshape(n, page, kvh, dh)
    qg = q.reshape(t, kvh, g, dh)
    qpos = jnp.arange(t)

    def step(carry, blk):
        m, l, acc = carry  # (kvh, g, t), (kvh, g, t), (kvh, g, t, dh)
        kb, vb, j = blk
        s = jnp.einsum("qkgd,skd->kgqs", qg, kb) * scale
        kpos = j * page + jnp.arange(page)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("kgqs,skd->kgqd", p, vb)
        return (m_new, l, acc), None

    init = (jnp.full((kvh, g, t), NEG, q.dtype), jnp.zeros((kvh, g, t), q.dtype),
            jnp.zeros((kvh, g, t, dh), q.dtype))
    (_, l, acc), _ = jax.lax.scan(step, init, (kp, vp, jnp.arange(n)))
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return out.transpose(2, 0, 1, 3).reshape(t, h * dh)


def block_apply(p, x, dims: Dims, page: int, temp):
    """One decoder block over one sequence x (T, d), attention over pages
    of ``page`` keys; the scores are scaled by ``temp`` (1: the model)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = rmsnorm(x, p["pre_norm"]["scale"], dims.norm_eps)
    a = p["attn"]
    q = bdense(h, a["q"]["w"]).reshape(t, dims.heads, dims.d_head)
    k = bdense(h, a["k"]["w"]).reshape(t, dims.kv_heads, dims.d_head)
    v = bdense(h, a["v"]["w"]).reshape(t, dims.kv_heads, dims.d_head)
    q = rope(q, pos, dims.rope_theta)
    k = rope(k, pos, dims.rope_theta)
    att = attention_paged(q, k, v, dims.q_scale * temp, page)
    x = x + bdense(att, a["o"]["w"])
    h = rmsnorm(x, p["pre_ffn_norm"]["scale"], dims.norm_eps)
    m = p["mlp"]
    u = jax.nn.silu(bdense(h, m["gate"]["w"])) * bdense(h, m["up"]["w"])
    return x + bdense(u, m["down"]["w"])


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


# ---------------------------------------------------------------------------
# serving: teacher-forced over prompts and served tokens, layer by layer
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _embed(key, tokens, dims: Dims, dtype):
    return weights.top(key, dims)["embed"]["table"].astype(dtype)[tokens]


@functools.partial(jax.jit, static_argnames=("dims", "dtype", "page"))
def _layer(key, i, x, temp, dims: Dims, dtype, page):
    """Block ``i`` over every sequence of x (n, T, d)."""
    p = _cast(weights.layer(key, i, dims), dtype)
    return jax.lax.map(lambda xs: block_apply(p, xs, dims, page, temp), x)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _head(key, h, dims: Dims, dtype):
    """Final norm and the tied head over rows h (R, d): (R, vocab)."""
    top = _cast(weights.top(key, dims), dtype)
    h = rmsnorm(h, top["final_norm"]["scale"], dims.norm_eps)
    return (h @ top["embed"]["table"][:dims.vocab].T).astype(F32)


def hidden(seed: int, dims: Dims, tokens: np.ndarray, page: int,
           dtype=F32, temp: float = 1.0):
    """The last block's output (n, T, d) over the token batch (n, T);
    causal, so right padding is inert."""
    key = seed_key(seed)
    with jax.default_matmul_precision(precision(dtype)):
        x = _embed(key, jnp.asarray(tokens), dims, dtype)
        for i in range(dims.layers):
            x = _layer(key, jnp.int32(i), x, jnp.asarray(temp, dtype),
                       dims, dtype, page)
    return x


def logits_at(seed: int, dims: Dims, x, rows: np.ndarray, dtype=F32,
              rows_per_call: int = 256):
    """Logits (len(rows), vocab) at flat positions ``rows`` of ``x``."""
    key = seed_key(seed)
    flat = x.reshape(-1, dims.d)[jnp.asarray(rows)]
    out = []
    with jax.default_matmul_precision(precision(dtype)):
        for s in range(0, len(rows), rows_per_call):
            chunk = flat[s:s + rows_per_call]
            pad = rows_per_call - chunk.shape[0]
            chunk = jnp.pad(chunk, ((0, pad), (0, 0)))
            out.append(np.asarray(_head(key, chunk, dims, dtype))[
                :rows_per_call - pad])
    return np.concatenate(out)


def _batch(seqs, pad_to: int):
    """Token batch (n, T) of prompt + served tokens but the last, and the
    flat row at which each served token is predicted, with its owner."""
    full = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in seqs]
    t = -(-max(len(f) for f in full) // pad_to) * pad_to
    tokens = np.zeros((len(seqs), t), np.int32)
    rows, owner = [], []
    for j, ((p, s), f) in enumerate(zip(seqs, full)):
        tokens[j, :len(f)] = f
        rows += [j * t + len(p) - 1 + m for m in range(len(s))]
        owner += [j] * len(s)
    return tokens, np.asarray(rows), np.asarray(owner)


def served_gaps(seed: int, dims: Dims, seqs, page: int,
                control: bool = False, temp: float = 1.0,
                pad_to: int = 256):
    """For each (prompt, served tokens) pair, teacher-forced: the gap by
    which each served token's reference logit lies below the reference's
    best at its position, one array per request.  With ``control=True``
    also the gaps of the tokens that the bfloat16 control puts first at
    the same positions.  Returns (gaps, control gaps or None)."""
    tokens, rows, owner = _batch(seqs, pad_to)
    served = np.concatenate([np.asarray(s, np.int64) for _, s in seqs])
    ref = logits_at(seed, dims, hidden(seed, dims, tokens, page, F32, temp),
                    rows)
    best = ref.max(-1)
    idx = np.arange(len(rows))

    def split(gap):
        return [gap[owner == j] for j in range(len(seqs))]

    gaps = split(best - ref[idx, served])
    if not control:
        return gaps, None
    x = hidden(seed, dims, tokens, page, BF16)
    ctl = logits_at(seed, dims, x, rows, BF16).argmax(-1)
    return gaps, split(best - ref[idx, ctl])


def divergence(seed: int, dims: Dims, seqs, page: int, kappa: float,
               pad_to: int = 256) -> list:
    """For each (prompt, served tokens) pair, with the softmax temperature
    moved by ``kappa`` either way: the first position of the sequence at
    which the reference's last hidden state changes (the sequence's length
    where it does not), and the widest gap by which the token that the
    moved reference puts first at a served position lies below the
    reference's best.  Returns [(position, gap)].

    Every matrix product of the w1a1 block takes signs, and its outputs
    are whole numbers, so the hidden state is exactly the same under the
    moved temperature unless a sign turned.  Where one turns, the sign of
    an attention output rested on a term at fp32's underflow threshold
    (binary attention is near one-hot), and the order and rounding of the
    arithmetic decides it.  Where the moved reference then also puts
    first a token far below the reference's best, the reference does not
    determine the served tokens to rounding from that position on."""
    tokens, rows, owner = _batch(seqs, pad_to)
    base = hidden(seed, dims, tokens, page)
    ref = logits_at(seed, dims, base, rows)
    best = ref.max(-1)
    first = np.array([len(p) + len(s) - 1 for p, s in seqs])
    worst = np.zeros(len(seqs))
    for temp in (1.0 + kappa, 1.0 - kappa):
        moved = hidden(seed, dims, tokens, page, F32, temp)
        diff = np.asarray(jnp.any(moved != base, axis=-1))
        top = logits_at(seed, dims, moved, rows).argmax(-1)
        del moved
        gap = best - ref[np.arange(len(rows)), top]
        for j in range(len(seqs)):
            hit = np.flatnonzero(diff[j, :first[j]])
            if hit.size:
                first[j] = min(first[j], int(hit[0]))
            worst[j] = max(worst[j], float(gap[owner == j].max()))
    return [(int(f), float(w)) for f, w in zip(first, worst)]
