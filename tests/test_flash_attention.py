"""The flash-attention kernels (interpret mode) against chunked_attention,
and the dispatch rule that picks between them per configuration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.layers as L
from repro.configs import ARCH_IDS, get_config
from repro.kernels import flash_attention as FA
from repro.models.config import INPUT_SHAPES


@pytest.mark.parametrize("b,kvh,g,hd", [(1, 2, 3, 64),     # smollm's heads
                                        (2, 1, 2, 128)],   # qwen3's heads
                         ids=["g3-hd64", "g2-hd128"])
def test_kernel_matches_chunked(b, kvh, g, hd, monkeypatch):
    """Output and the q, k, v gradients of the kernels equal the jnp path's
    with bfloat16 inputs, over two kv blocks (the causal skip) and a
    diagonal cut into row groups; blocks a quarter of the chip's, so that
    the interpreter takes seconds."""
    s = 512
    monkeypatch.setattr(FA, "block_sizes", lambda s: (256, 128))
    ks = jax.random.split(jax.random.PRNGKey(hd), 4)
    q = jax.random.normal(ks[0], (b, s, kvh, g, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kvh, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kvh, hd)).astype(jnp.bfloat16)
    ct = jax.random.normal(ks[3], q.shape).astype(jnp.bfloat16)

    @jax.jit
    def run_kernel(q, k, v):
        out, pull = jax.vjp(
            lambda *a: FA.flash_attention(*a, interpret=True), q, k, v)
        return out, pull(ct)

    @jax.jit
    def run_chunked(q, k, v):
        out, pull = jax.vjp(L.chunked_attention, q, k, v)
        return out, pull(ct)

    out_k, grads_k = run_kernel(q, k, v)
    out_c, grads_c = run_chunked(q, k, v)
    assert out_k.dtype == q.dtype and out_k.shape == q.shape
    for name, a, c in [("out", out_k, out_c)] + list(
            zip(("dq", "dk", "dv"), grads_k, grads_c)):
        a, c = np.asarray(a, np.float32), np.asarray(c, np.float32)
        rel = np.linalg.norm(a - c) / np.linalg.norm(c)
        assert rel < 1e-2, (name, rel)


#: (batch, sequence) each configuration trains at in the benchmark's cells;
#: the others at the train_4k input shape
CELL_SHAPES = {"smollm-135m": (8, 2048), "qwen3-0.6b": (1, 4096)}

#: the path of each attention layer kind in train mode on a TPU
EXPECTED = {
    "smollm-135m": {"A": "kernel"},
    "qwen3-0.6b": {"A": "kernel"},
    "yi-9b": {"A": "kernel"},
    "chameleon-34b": {"A": "kernel"},
    "gemma2-9b": {"L": "chunked", "A": "chunked"},      # window, soft-cap
    "deepseek-moe-16b": {"D": "kernel", "E": "kernel"},
    "granite-moe-3b-a800m": {"E": "kernel"},
    "jamba-v0.1-52b": {"A": "kernel"},
    "mamba2-1.3b": {},
    "whisper-small": {"A": "kernel", "encoder": "chunked"},   # non-causal
}


def _train_paths(cfg):
    b, s = CELL_SHAPES.get(cfg.arch_id, (1, INPUT_SHAPES["train_4k"].seq_len))
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    g = cfg.n_heads // max(kvh, 1)
    paths = {}
    for code in dict.fromkeys(cfg.prelude + cfg.period):
        if code in "ALED":
            paths[code] = L.attention_path(
                (b, s, kvh, g, hd), (b, s, kvh, hd), causal=True,
                window=cfg.sliding_window if code == "L" else None,
                softcap=cfg.attn_softcap, q_offset=0, k_offset=0)
    if cfg.is_encoder_decoder:
        t = cfg.encoder_frames
        paths["encoder"] = L.attention_path(
            (b, t, kvh, g, hd), (b, t, kvh, hd), causal=False, window=None,
            softcap=None, q_offset=0, k_offset=0)
    return paths


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_attention_path_per_config(arch, monkeypatch):
    """On a TPU backend every configuration's train-mode attention takes
    the path EXPECTED names: the kernel for causal self-attention, the jnp
    path for windows, soft-caps and non-causal attention."""
    monkeypatch.setattr(L, "default_interpret", lambda: False)
    assert _train_paths(get_config(arch)) == EXPECTED[arch]


@pytest.mark.parametrize("case", ["cpu", "seq_sharded", "cross", "window",
                                  "softcap", "head_dim", "ragged"])
def test_attention_path_falls_back(case, monkeypatch):
    """What the kernels do not cover keeps chunked_attention: the CPU, the
    sequence-sharded path's traced offset, cross attention over another
    length, a window, a soft-cap, head_dim 96, a sequence not of whole
    128-lane blocks."""
    if case != "cpu":
        monkeypatch.setattr(L, "default_interpret", lambda: False)
    b, s, kvh, g, hd = 1, 2048, 3, 3, 64
    kw = dict(causal=True, window=None, softcap=None, q_offset=0, k_offset=0)
    sk = s
    if case == "seq_sharded":
        kw["q_offset"] = jnp.asarray(0) + 2 * s      # pos_offset + r * s_local
    elif case == "cross":
        sk = 1536
    elif case == "window":
        kw["window"] = 4096
    elif case == "softcap":
        kw["softcap"] = 50.0
    elif case == "head_dim":
        hd = 96
    elif case == "ragged":
        s = sk = 1488
    assert L.attention_path((b, s, kvh, g, hd), (b, sk, kvh, hd),
                            **kw) == "chunked"
    monkeypatch.setattr(L, "default_interpret", lambda: False)
    if case == "cpu":
        assert L.attention_path((b, s, kvh, g, hd), (b, sk, kvh, hd),
                                **kw) == "kernel"
