"""Plain reference of the Llama/Qwen3 family's training, as a
configuration states it.

Written from the configuration alone: it imports nothing of the program
under test and takes nothing the program made.  It draws its own weights
from the seed by the initialisation the configuration states and trains on
the same token rows the timed path was fed, with Adam.

A configuration file names its reference module under ``reference``
(``bench/<reference>.py``; this one where the key is absent).  Every such
module gives what the harness asks of a model: ``Model.from_config``,
``run_reference``, ``check_program`` (the file's published keys against
the program's ModelConfig) and ``flops_per_token`` / ``param_count``;
for a ring of nodes (``bench/ring.py``) also ``init_params``, ``grad_fn``,
``adam`` and ``leaf_specs`` (the wire kernels' rows); and,
where the family has kernels of its own, ``kernel_counts(conf, traffic)``
(bench/counts.py).

The configurations state float32 weights, activations, optimizer state and
accumulation, with float32 matrix products at the TPU's default precision
(``Precision.DEFAULT``: operands rounded to bfloat16, products accumulated
in float32); the reference computes just so (``precision="f32"``).  The
control is the same computation one precision lower: weights and
activations in bfloat16 (``precision="bf16"``), with float32 gradients,
optimizer and loss.

The forward pass is that of a Llama-style decoder (SmolLM, Qwen3):
RMSNorm with a (1 + w) gain, grouped-query attention with rotary positions
(halves rotated), optional RMSNorm on queries and keys (Qwen3), SwiGLU MLP,
tied input/output embedding, mean token cross-entropy.  It runs one
sequence at a time, one layer at a time (recomputed in the backward pass),
and the attention and the vocabulary head in blocks of rows, so that it
fits one chip after the program's state is freed.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Model", "leaf_specs", "init_params", "grad_fn", "adam",
           "run_reference", "check_program", "matmul_params", "param_count",
           "flops_per_token"]

ROW_BLOCK = 1024          # rows of a vocabulary-head block
QUERY_BLOCK = 512         # query rows of an attention block
KEY_BLOCK = 1024          # keys of an attention block
MASKED = -1e30            # a masked score
#: the dtype of weights and activations at each precision
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes the reference needs, in the configuration file's terms
    (Hugging Face ``config.json`` key names)."""
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    qk_norm: bool

    @classmethod
    def from_config(cls, conf: dict) -> "Model":
        heads = conf["num_attention_heads"]
        return cls(
            hidden_size=conf["hidden_size"],
            intermediate_size=conf["intermediate_size"],
            num_hidden_layers=conf["num_hidden_layers"],
            num_attention_heads=heads,
            num_key_value_heads=conf["num_key_value_heads"],
            head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
            vocab_size=conf["vocab_size"],
            rms_norm_eps=conf["rms_norm_eps"],
            rope_theta=conf["rope_theta"],
            qk_norm=conf["model_type"] == "qwen3")


def leaf_specs(m: Model) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, shape, init) of every parameter, in the order the seed's keys
    are split over them (names sorted level by level).  Layer weights are
    stacked over the layers; ``init`` is ``normal`` (std 1/sqrt(fan-in),
    fan-in the second-to-last dimension) or ``zeros`` (a norm's gain
    offset, applied as 1 + w)."""
    d, f, L = m.hidden_size, m.intermediate_size, m.num_hidden_layers
    q, kv = m.num_attention_heads * m.head_dim, m.num_key_value_heads * m.head_dim
    specs = [("embed.table", (m.vocab_size, d), "normal"),
             ("final_norm", (d,), "zeros")]
    if m.qk_norm:
        specs += [("layers.attn.k_norm", (L, m.head_dim), "zeros"),
                  ("layers.attn.q_norm", (L, m.head_dim), "zeros")]
    specs += [("layers.attn.wk", (L, d, kv), "normal"),
              ("layers.attn.wo", (L, q, d), "normal"),
              ("layers.attn.wq", (L, d, q), "normal"),
              ("layers.attn.wv", (L, d, kv), "normal"),
              ("layers.mlp.w_down", (L, f, d), "normal"),
              ("layers.mlp.w_gate", (L, d, f), "normal"),
              ("layers.mlp.w_up", (L, d, f), "normal"),
              ("layers.norm1", (L, d), "zeros"),
              ("layers.norm2", (L, d), "zeros")]
    return specs


def init_params(m: Model, seed: int) -> dict[str, jax.Array]:
    """All weights from the seed, in one jitted call on the device."""
    specs = leaf_specs(m)

    def make(key):
        keys = jax.random.split(key, len(specs))
        out = {}
        for k, (name, shape, init) in zip(keys, specs):
            if init == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = shape[-2]
                out[name] = jax.random.normal(k, shape, jnp.float32) * (
                    1.0 / math.sqrt(fan_in))
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _mm(spec, a, b, out=None):
    """A matrix product at the TPU's default precision, accumulated in
    float32, returned in ``out`` (the operands' dtype by default)."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32).astype(
                          out or a.dtype)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)
            * (1.0 + w).astype(x.dtype))


def _rope(x, theta):
    """x: (s, h, hd); rotate the two halves of the head dimension."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v):
    """Causal softmax attention with flash-attention numerics (Dao et al.
    2022), one block of QUERY_BLOCK query rows at a time: softmax
    statistics in float32, kept online over blocks of KEY_BLOCK keys; each
    block's probabilities, unnormalised, are the product's operand, and the
    sum is normalised at the end.  q: (s, kvh, g, hd); k, v: (s, kvh, hd)."""
    s, kvh, g, hd = q.shape
    cq, ck = min(QUERY_BLOCK, s), min(KEY_BLOCK, s)
    nq, nk = s // cq, s // ck
    kb = k.reshape(nk, ck, kvh, hd)
    vb = v.reshape(nk, ck, kvh, hd)

    @jax.checkpoint
    def q_block(i, qb):
        rows = i * cq + jnp.arange(cq)

        def k_block(carry, j_kv):
            m, l, acc = carry
            j, kj, vj = j_kv
            sc = _mm("qhgd,khd->hgqk", qb, kj, jnp.float32) * (
                1.0 / math.sqrt(hd))
            mask = rows[:, None] >= (j * ck + jnp.arange(ck))[None, :]
            sc = jnp.where(mask[None, None], sc, MASKED)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + _mm("hgqk,khd->hgqd",
                                              p.astype(q.dtype), vj,
                                              jnp.float32)
            return (m_new, l * corr + jnp.sum(p, axis=-1), acc), None

        m0 = jnp.full((kvh, g, cq), MASKED, jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_block, (m0, jnp.zeros_like(m0),
                      jnp.zeros((kvh, g, cq, hd), jnp.float32)),
            (jnp.arange(nk), kb, vb))
        return jnp.transpose(acc / l[..., None], (2, 0, 1, 3))

    out = jax.lax.map(lambda a: q_block(*a),
                      (jnp.arange(nq), q.reshape(nq, cq, kvh, g, hd)))
    return out.reshape(s, kvh, g, hd).astype(q.dtype)


def _layer(x, p, m: Model):
    s = x.shape[0]
    hd, kvh = m.head_dim, m.num_key_value_heads
    g = m.num_attention_heads // kvh
    h = _rms(x, p["norm1"], m.rms_norm_eps)
    q = _mm("sd,de->se", h, p["attn.wq"]).reshape(s, m.num_attention_heads, hd)
    k = _mm("sd,de->se", h, p["attn.wk"]).reshape(s, kvh, hd)
    v = _mm("sd,de->se", h, p["attn.wv"]).reshape(s, kvh, hd)
    if m.qk_norm:
        q = _rms(q, p["attn.q_norm"], m.rms_norm_eps)
        k = _rms(k, p["attn.k_norm"], m.rms_norm_eps)
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    o = _attention(q.reshape(s, kvh, g, hd), k, v).reshape(s, -1)
    x = x + _mm("se,ed->sd", o, p["attn.wo"])
    h = _rms(x, p["norm2"], m.rms_norm_eps)
    a = jax.nn.silu(_mm("sd,df->sf", h, p["mlp.w_gate"])) * _mm(
        "sd,df->sf", h, p["mlp.w_up"])
    return x + _mm("sf,fd->sd", a, p["mlp.w_down"])


def _seq_loss(w, tokens, labels, m: Model, dtype):
    """Mean next-token cross-entropy of one sequence, with weights and
    activations in ``dtype``."""
    w = {k: v.astype(dtype) for k, v in w.items()}
    table = w["embed.table"]
    x = jnp.take(table, tokens, axis=0)
    layers = {k[len("layers."):]: v for k, v in w.items()
              if k.startswith("layers.")}

    def body(x, p):
        return _layer(x, p, m), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, layers)
    x = _rms(x, w["final_norm"], m.rms_norm_eps)
    s = x.shape[0]
    cr = min(ROW_BLOCK, s)

    @jax.checkpoint
    def head(a):
        xb, lb = a
        logits = _mm("sd,vd->sv", xb, table).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - tgt)

    nll = jax.lax.map(head, (x.reshape(s // cr, cr, -1),
                             labels.reshape(s // cr, cr)))
    return jnp.sum(nll) / s


def grad_fn(m: Model, precision: str):
    """``precision``: ``f32`` (the reference) or ``bf16`` (the control)."""
    dtype = DTYPES[precision]

    @jax.jit
    def grad(params, tokens, labels):
        """Mean loss and its gradient over a node's rows, one sequence at
        a time."""
        vg = jax.value_and_grad(
            lambda p, t, l: _seq_loss(p, t, l, m, dtype))

        def body(acc, tl):
            loss, g = vg(params, *tl)
            return (acc[0] + loss,
                    jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, params))
        (loss, g), _ = jax.lax.scan(body, zero, (tokens, labels))
        n = tokens.shape[0]
        return loss / n, jax.tree.map(lambda a: a / n, g)

    return grad


# ---------------------------------------------------------------------------
# optimizer and training
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(2, 3))
def adam(params, grads, mu, nu, t, lr, b1, b2, eps):
    t = t + 1
    b1t = 1.0 - b1 ** t.astype(jnp.float32)
    b2t = 1.0 - b2 ** t.astype(jnp.float32)

    def upd(p, g, mo, no):
        mo = b1 * mo + (1 - b1) * g
        no = b2 * no + (1 - b2) * g * g
        return p - lr * ((mo / b1t) / (jnp.sqrt(no / b2t) + eps)), mo, no

    out = {k: upd(params[k], grads[k], mu[k], nu[k]) for k in params}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()}, t)


@jax.jit
def _diff(a, b):
    return {k: a[k] - b[k] for k in a}


def run_reference(m: Model, opt: dict, seed: int,
                  batches: list[tuple[np.ndarray, np.ndarray]],
                  precision: str = "f32") -> dict:
    """Train one node for ``len(batches)`` steps on ``batches[step]``, its
    (tokens, labels) rows.  Returns the loss of every step, the first
    step's gradient and the change of the parameters over all the steps,
    the last two as host arrays per leaf."""
    grad = grad_fn(m, precision)
    lr, b1, b2, eps = (np.float32(opt[k]) for k in ("lr", "b1", "b2", "eps"))
    x0 = init_params(m, seed)
    p = x0
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    t = jnp.zeros((), jnp.int32)
    losses, grad1 = [], None
    for tokens, labels in batches:
        loss, g = grad(p, tokens, labels)
        losses.append(loss)
        if grad1 is None:
            grad1 = jax.tree.map(np.asarray, g)
        p, mu, nu, t = adam(p, g, mu, nu, t, lr, b1, b2, eps)
        del g
    change = jax.tree.map(np.asarray, _diff(p, x0))
    return {"loss": [float(v) for v in losses], "grad1": grad1,
            "change": change}


# ---------------------------------------------------------------------------
# the configuration file against the program, and the model's counts
# ---------------------------------------------------------------------------

def check_program(conf: dict, cfg) -> dict:
    """The published keys of ``conf`` that the program's ModelConfig
    ``cfg`` builds otherwise, as {key: (file, program)}."""
    built = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
             "num_hidden_layers": cfg.n_layers,
             "num_attention_heads": cfg.n_heads,
             "num_key_value_heads": cfg.n_kv_heads,
             "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab_size,
             "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
             "tie_word_embeddings": cfg.tie_embeddings}
    bad = {k: (conf[k], v) for k, v in built.items() if conf[k] != v}
    if cfg.qk_norm != (conf["model_type"] == "qwen3"):
        bad["model_type"] = (conf["model_type"], f"qk_norm={cfg.qk_norm}")
    return bad


def matmul_params(conf: dict) -> int:
    """Matrix weights, each once; the (tied) vocabulary head once."""
    m = Model.from_config(conf)
    q = m.num_attention_heads * m.head_dim
    kv = m.num_key_value_heads * m.head_dim
    d = m.hidden_size
    per_layer = 2 * d * q + 2 * d * kv + 3 * d * m.intermediate_size
    return m.num_hidden_layers * per_layer + m.vocab_size * d


def param_count(conf: dict) -> int:
    """Every parameter: matrices and norm gains (``leaf_specs``)."""
    return sum(math.prod(shape) for _, shape, _ in
               leaf_specs(Model.from_config(conf)))


def flops_per_token(conf: dict, seq_len: int) -> float:
    """Model FLOPs per training token (PaLM, Chowdhery et al. 2022, app.
    B): ``6 N + 12 L H Q T``, with N the matrix weights (``matmul_params``),
    L layers, H query heads of size Q and T the sequence length.
    Recomputed operations are not counted."""
    m = Model.from_config(conf)
    return (6.0 * matmul_params(conf) + 12.0 * m.num_hidden_layers
            * m.num_attention_heads * m.head_dim * seq_len)
