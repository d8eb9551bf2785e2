"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret mode.
The hypothesis property tests on the quantization wire format live in
test_property_based.py (importorskip-guarded for bare envs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.dequant_combine import dequant_combine_pallas
from repro.kernels.quantize import (BLOCK, TILE_N, _scale_to_bytes,
                                    quantize_blocks_pallas)

SHAPES = [(32, 128), (32, 512), (64, 512), (96, 256), (320, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("mode", ["adaptive", "fixed"])
def test_quantize_matches_oracle(shape, dtype, mode):
    key = jax.random.PRNGKey(hash((shape, str(dtype), mode)) % 2**31)
    y = (jax.random.normal(key, shape) * 2.0).astype(dtype).astype(jnp.float32)
    noise = jax.random.uniform(jax.random.fold_in(key, 1), shape)
    step = jnp.float32(0.05) if mode == "fixed" else None
    c_p, s_p = quantize_blocks_pallas(y, noise, fixed_step=step, interpret=True)
    c_r, s_r = ref.quantize_blocks_ref(y, noise, fixed_step=step)
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r), rtol=1e-6)


@pytest.mark.parametrize("shape", SHAPES[:3])
def test_dequant_combine_matches_oracle(shape):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    y = jax.random.normal(ks[0], shape)
    noise = jax.random.uniform(ks[1], shape)
    codes, scales = ref.quantize_blocks_ref(y, noise)
    xt = jax.random.normal(ks[2], shape)
    m = jax.random.normal(ks[3], shape)
    args = (codes, scales, codes, scales, codes, scales, xt, m,
            0.5, 0.25, jnp.float32(0.37))
    outs_p = dequant_combine_pallas(*args, interpret=True)
    outs_r = ref.dequant_combine_ref(*args)
    for a, b in zip(outs_p, outs_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# Flat wire payload (codes + scales in one byte buffer)
# ---------------------------------------------------------------------------

def test_payload_roundtrip():
    """pack_payload -> unpack_payload is the identity on (codes, scales)."""
    key = jax.random.PRNGKey(5)
    y = jax.random.normal(key, (64, BLOCK)) * 3.0
    noise = jax.random.uniform(jax.random.fold_in(key, 1), y.shape)
    codes, scales = ref.quantize_blocks_ref(y, noise)
    payload = ops.pack_payload(codes, scales)
    assert payload.shape == (64, ops.payload_width())
    assert payload.dtype == jnp.uint8
    c2, s2 = ops.unpack_payload(payload)
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(scales))


def test_quantize_payload_matches_quantize_then_pack():
    """The fused payload emitter is bit-identical to quantize + pack, in
    both scale modes (the jnp dispatch path; the pallas kernel is covered
    by test_quantize_payload_pallas_matches_oracle)."""
    key = jax.random.PRNGKey(6)
    y = jax.random.normal(key, (96, BLOCK))
    noise = jax.random.uniform(jax.random.fold_in(key, 1), y.shape)
    for step in (None, jnp.float32(0.05)):
        pl = ops.quantize_payload(y, noise, fixed_step=step)
        ref_pl = ops.pack_payload(*ref.quantize_blocks_ref(y, noise,
                                                           fixed_step=step))
        np.testing.assert_array_equal(np.asarray(pl), np.asarray(ref_pl))


def test_payload_byte_order():
    """Pin the scale-byte order: the encode kernels' shift-based byte image
    (``_scale_to_bytes``) is XLA's bitcast (least-significant byte first),
    which ``ops.unpack_payload`` and the combine kernels decode — the
    contract that keeps the Pallas payload kernels bit-identical to the jnp
    oracle."""
    scales = jnp.asarray([[1.5], [-2.25], [3e-7], [1e30]], jnp.float32)
    codes = jnp.zeros((4, BLOCK), jnp.int8)
    payload = ops.pack_payload(codes, scales)
    np.testing.assert_array_equal(np.asarray(_scale_to_bytes(scales)),
                                  np.asarray(payload[:, BLOCK:]))
    np.testing.assert_array_equal(np.asarray(ops.unpack_payload(payload)[1]),
                                  np.asarray(scales))


def test_dequant_combine_payload_matches_unpacked():
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 4)
    y = jax.random.normal(ks[0], (64, BLOCK))
    noise = jax.random.uniform(ks[1], y.shape)
    codes, scales = ref.quantize_blocks_ref(y, noise)
    payload = ops.pack_payload(codes, scales)
    xt = jax.random.normal(ks[2], y.shape)
    m = jax.random.normal(ks[3], y.shape)
    outs_p = ops.dequant_combine_payload(payload, payload, payload, xt, m,
                                         0.5, 0.25, jnp.float32(1.0))
    outs_r = ref.dequant_combine_ref(codes, scales, codes, scales, codes,
                                     scales, xt, m, 0.5, 0.25,
                                     jnp.float32(1.0))
    for a, b in zip(outs_p, outs_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("mode", ["adaptive", "fixed"])
def test_quantize_payload_pallas_matches_oracle(shape, mode):
    """The fused payload-emitting kernel: byte-exact vs quantize + pack."""
    key = jax.random.PRNGKey(hash((shape, mode)) % 2**31)
    y = jax.random.normal(key, shape) * 2.0
    noise = jax.random.uniform(jax.random.fold_in(key, 1), shape)
    step = jnp.float32(0.05) if mode == "fixed" else None
    from repro.kernels.quantize import quantize_payload_pallas
    pl_k = quantize_payload_pallas(y, noise, fixed_step=step, interpret=True)
    pl_r = ops.pack_payload(*ref.quantize_blocks_ref(y, noise,
                                                     fixed_step=step))
    np.testing.assert_array_equal(np.asarray(pl_k), np.asarray(pl_r))


@pytest.mark.parametrize("shape", SHAPES[:3])
def test_dequant_combine_payload_pallas_matches_oracle(shape):
    """Byte payloads in (scales read out before the kernel), combine out."""
    from repro.kernels.dequant_combine import dequant_combine_payload_pallas
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 6)
    y = jax.random.normal(ks[0], shape)
    noise = jax.random.uniform(ks[1], shape)
    pls = []
    for i in (2, 3):
        c, s = ref.quantize_blocks_ref(
            jax.random.normal(ks[i], shape), noise)
        pls.append(ops.pack_payload(c, s))
    codes, scales = ref.quantize_blocks_ref(y, noise)
    p_self = ops.pack_payload(codes, scales)
    xt = jax.random.normal(ks[4], shape)
    m = jax.random.normal(ks[5], shape)
    outs_k = dequant_combine_payload_pallas(p_self, pls[0], pls[1], xt, m,
                                            0.5, 0.25, jnp.float32(0.37),
                                            interpret=True)
    c_l, s_l = ops.unpack_payload(pls[0], shape[1])
    c_r, s_r = ops.unpack_payload(pls[1], shape[1])
    outs_r = ref.dequant_combine_ref(codes, scales, c_l, s_l, c_r, s_r,
                                     xt, m, 0.5, 0.25, jnp.float32(0.37))
    for a, b in zip(outs_k, outs_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_quantize_roundtrip_error_bound():
    """Adaptive: |dec - y| <= scale per element (one grid step)."""
    key = jax.random.PRNGKey(3)
    y = jax.random.normal(key, (64, BLOCK)) * 10
    noise = jax.random.uniform(jax.random.fold_in(key, 1), y.shape)
    codes, scales = ops.quantize_blocks(y, noise)
    dec = codes.astype(jnp.float32) * scales
    assert float(jnp.max(jnp.abs(dec - y) / scales)) <= 1.0 + 1e-5


def test_blockify_roundtrip():
    for n in (1, 511, 512, 513, 100_000):
        flat = jnp.arange(n, dtype=jnp.float32)
        blocks = ops.blockify(flat)
        assert blocks.shape[0] % TILE_N == 0
        np.testing.assert_array_equal(np.asarray(ops.unblockify(blocks, n)),
                                      np.asarray(flat))


@pytest.mark.parametrize("b,s,kvh,g,hd", [(2, 64, 2, 2, 32), (1, 128, 4, 1, 64),
                                          (3, 96, 1, 8, 16)])
def test_gqa_decode_ref_matches_dense_softmax(b, s, kvh, g, hd):
    """The flash-decode oracle must equal a plain softmax attention."""
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, kvh, g, hd))
    k = jax.random.normal(ks[1], (b, s, kvh, hd))
    v = jax.random.normal(ks[2], (b, s, kvh, hd))
    valid = jnp.arange(s) < (s - 7)
    m, l, acc = ref.gqa_decode_ref(q, k, v, valid)
    out = acc / l[..., None]
    # dense reference
    import math
    scores = jnp.einsum("bhgd,bkhd->bhgk", q, k) / math.sqrt(hd)
    scores = jnp.where(valid[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    expected = jnp.einsum("bhgk,bkhd->bhgd", probs, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_gqa_decode_shard_combine():
    """Partials from two shards combine to the full-cache answer."""
    from repro.models.layers import combine_decode_partials
    from repro.models.sharding import local_context
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    b, s, kvh, g, hd = 2, 128, 2, 2, 32
    q = jax.random.normal(ks[0], (b, kvh, g, hd))
    k = jax.random.normal(ks[1], (b, s, kvh, hd))
    v = jax.random.normal(ks[2], (b, s, kvh, hd))
    valid = jnp.ones((s,), bool)
    m_f, l_f, acc_f = ref.gqa_decode_ref(q, k, v, valid)
    full = acc_f / l_f[..., None]
    # two halves combined with the log-sum-exp rule
    h = s // 2
    m1, l1, a1 = ref.gqa_decode_ref(q, k[:, :h], v[:, :h], valid[:h])
    m2, l2, a2 = ref.gqa_decode_ref(q, k[:, h:], v[:, h:], valid[h:])
    mg = jnp.maximum(m1, m2)
    lg = l1 * jnp.exp(m1 - mg) + l2 * jnp.exp(m2 - mg)
    ag = a1 * jnp.exp(m1 - mg)[..., None] + a2 * jnp.exp(m2 - mg)[..., None]
    np.testing.assert_allclose(np.asarray(ag / lg[..., None]),
                               np.asarray(full), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# gqa_decode Pallas kernel (interpret) vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,kvh,g,hd,S,cap", [
    (2, 2, 4, 128, 1024, None),      # GQA, 2 S-tiles
    (1, 4, 1, 64, 512, 30.0),        # MHA-ish + softcap, single tile
    (2, 1, 7, 128, 2048, None),      # odd group size (pad to 8), 4 tiles
    (1, 8, 2, 128, 512, None),       # many kv heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_decode_pallas_matches_oracle(b, kvh, g, hd, S, cap, dtype):
    key = jax.random.PRNGKey(42)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, kvh, g, hd), dtype)
    k = jax.random.normal(ks[1], (b, S, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (b, S, kvh, hd), dtype)
    valid = jnp.arange(S) < (S - 37)
    mp, lp, ap = ops.gqa_decode(q, k, v, valid, softcap=cap, use_pallas=True)
    mr, lr, ar = ref.gqa_decode_ref(q, k, v, valid, softcap=cap)
    # partials may differ in m by the blockwise path; the combined outputs
    # and log-sum-exp values are the invariants
    outp = np.asarray(ap) / np.maximum(np.asarray(lp), 1e-30)[..., None]
    outr = np.asarray(ar) / np.maximum(np.asarray(lr), 1e-30)[..., None]
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(outp, outr, atol=tol, rtol=tol)
    lse_p = np.asarray(mp) + np.log(np.maximum(np.asarray(lp), 1e-30))
    lse_r = np.asarray(mr) + np.log(np.maximum(np.asarray(lr), 1e-30))
    np.testing.assert_allclose(lse_p, lse_r, atol=5e-5 if dtype == jnp.float32 else 5e-2)


def test_gqa_decode_pallas_all_masked_tile():
    """Tiles that are fully masked (beyond the causal frontier) must not
    poison the running accumulator."""
    b, kvh, g, hd, S = 1, 2, 2, 128, 2048
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, kvh, g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, S, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, S, kvh, hd), jnp.float32)
    valid = jnp.arange(S) < 100            # only the first tile has any valid
    mp, lp, ap = ops.gqa_decode(q, k, v, valid, use_pallas=True)
    mr, lr, ar = ref.gqa_decode_ref(q, k, v, valid)
    outp = np.asarray(ap) / np.asarray(lp)[..., None]
    outr = np.asarray(ar) / np.asarray(lr)[..., None]
    np.testing.assert_allclose(outp, outr, atol=1e-5, rtol=1e-5)
    assert np.all(np.isfinite(outp))
