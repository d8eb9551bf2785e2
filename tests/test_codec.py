"""Wire-codec subsystem (core.codec + kernels/bitpack.py).

Covered invariants:
  * payload byte accounting is exact per codec (widths, payload_bytes,
    runtime wire_bytes_per_step), and the sub-byte/sparse codecs genuinely
    shrink the wire: int4 == 2x, int2/topk ~3.97x fewer bytes than int8
  * the refactored int8 path is byte-for-byte the pre-refactor composition
    pack_payload(quantize_blocks_ref(...)) and its combine matches
    ref.dequant_combine_ref — the WireCodec interface is bit-invisible
  * jnp ref == Pallas(interpret) bit-for-bit for every codec, both
    quantization modes, whole-buffer and chunk views (static row_offset /
    n_rows over full-height operands)
  * exact rounding-probability (binomial) unbiasedness for the dense
    sub-byte codecs: P(round up) == frac(y / scale) elementwise
  * top-k: per-element selection frequency == |y_i| / sum_stratum|y|,
    conditional transmitted value == y_i / p_i, E[decode(encode(z))] == z
    (fixed-seed Monte Carlo)
  * adaptive-mode scales never clip (the bf16 round-up guarantee)
  * AdaptiveBitController: budget filter, fidelity targeting from the
    amplified grid Delta_0 / k^gamma, immediate up-switch on overflow,
    patience-gated down-switches
  * ConsensusConfig validation: codec names, per-leaf/compressed_dgd pins
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec as C
from repro.kernels import bitpack, ops as kops, ref

ALL_CODECS = ("int8", "int4", "int2", "topk")
NEW_CODECS = ("int4", "int2", "topk")


def _mk(n=64, seed=0, spread=1.0):
    rng = np.random.default_rng(seed)
    y = jnp.asarray(rng.standard_normal((n, kops.BLOCK)) * spread, jnp.float32)
    return rng, y


def _noise(rng, n, codec):
    return jnp.asarray(rng.random((n, codec.noise_cols(kops.BLOCK))),
                       jnp.float32)


# ---------------------------------------------------------------------------
# payload geometry / byte accounting
# ---------------------------------------------------------------------------

def test_payload_byte_accounting_exact():
    b = kops.BLOCK
    widths = {"int8": b + 4,            # codes + fp32 scale
              "int4": b // 2 + 2,       # 2 codes/byte + bf16 scale
              "int2": b // 4 + 2,       # 4 codes/byte + bf16 scale
              "topk": b // 8 + 64 + 2}  # bitmap + k values + bf16 scale
    rng, y = _mk()
    for name, w in widths.items():
        cd = C.by_name(name)
        assert cd.payload_width(b) == w, name
        assert cd.payload_bytes(640, b) == 640 * w
        pay = cd.encode_payload(y, _noise(rng, y.shape[0], cd))
        assert pay.shape == (y.shape[0], w) and pay.dtype == jnp.uint8, name
    # the acceptance ratios: int4 exactly 2x, int2/topk > 3.9x fewer bytes
    int8_w = widths["int8"]
    assert int8_w / widths["int4"] >= 2.0
    assert int8_w / widths["int2"] > 3.9
    assert int8_w / widths["topk"] > 3.9
    for name in NEW_CODECS:   # strictly fewer, monotone vs int8
        assert widths[name] < widths["int8"]


def test_topk_k_spec_grammar_and_bytes():
    """"topk:k=<int>" parses through by_name with the exact byte formula
    BLOCK/8 (bitmap) + k (int8 values) + 2 (bf16 scale) = 64 + k + 2 at
    BLOCK=512; k=64 canonicalizes to the bare "topk" name so plan
    fragments and run-merge lookups round-trip."""
    b = kops.BLOCK
    for k in (16, 32, 64, 128, 256):
        cd = C.by_name(f"topk:k={k}")
        assert cd.k == k
        assert cd.payload_width(b) == b // 8 + k + 2, k
    assert C.by_name("topk:k=128").payload_width(b) == 64 + 128 + 2
    # name canonicalization: default k round-trips to the bare spec
    assert C.by_name("topk:k=64").name == "topk"
    assert C.by_name("topk:k=128").name == "topk:k=128"
    assert C.by_name(C.by_name("topk:k=128").name).k == 128
    # more k -> more bytes, denser payloads, monotone
    w16, w256 = (C.by_name(f"topk:k={k}").payload_width(b) for k in (16, 256))
    assert w16 < w256
    # grammar errors name the spec
    with pytest.raises(KeyError, match="topk:k="):
        C.by_name("topk:k=x")
    with pytest.raises(ValueError, match="k must divide"):
        C.by_name("topk:k=63")
    with pytest.raises(KeyError):
        C.by_name("topk:j=64")
    # a parameterized codec encodes/decodes with the widened payload
    rng, y = _mk()
    cd = C.by_name("topk:k=128")
    pay = cd.encode_payload(y, _noise(rng, y.shape[0], cd))
    assert pay.shape == (y.shape[0], b // 8 + 128 + 2)
    dq = cd.decode_payload(pay)
    assert dq.shape == y.shape
    # k=128 keeps at most 128 nonzeros per block — and more than k=64 would
    nz = np.count_nonzero(np.asarray(dq), axis=1)
    assert nz.max() <= 128
    # every CODEC_NAMES entry is a valid by_name spec (the registry's
    # contract with the spec grammar and the CLI help text)
    for name in C.CODEC_NAMES:
        C.by_name(name)


def test_runtime_wire_bytes_use_codec_width():
    from repro.core.distributed import ConsensusConfig, ConsensusRuntime
    from repro.core.wire import WireLayout
    from repro.models.sharding import ParallelContext
    ctx = ParallelContext(tp=1, data_size=4, n_nodes=4)
    tree = {"w": jnp.zeros((40 * kops.BLOCK + 7,))}
    layout = WireLayout.for_tree(tree)
    got = {}
    for name in ALL_CODECS:
        rt = ConsensusRuntime(
            ConsensusConfig(algorithm="adc_dgd", wire_codec=name), ctx)
        got[name] = rt.wire_bytes_per_step(layout.n_elements, layout=layout)
        assert got[name] == 2 * layout.n_rows * C.by_name(name).payload_width()
        # collectives are codec-independent
        assert rt.collectives_per_step(1) == 2.0
    assert got["int8"] / got["int4"] >= 2.0
    assert got["int8"] / got["topk"] >= 2.0
    assert got["int2"] < got["int4"] < got["int8"]


def test_config_validation():
    from repro.core.distributed import ConsensusConfig
    with pytest.raises(ValueError, match="wire_codec"):
        ConsensusConfig(wire_codec="int3")
    with pytest.raises(ValueError, match="per-leaf"):
        ConsensusConfig(wire_codec="int4", wire_packing="per_leaf")
    with pytest.raises(ValueError, match="compressed_dgd"):
        ConsensusConfig(algorithm="compressed_dgd", wire_codec="topk")
    with pytest.raises(ValueError, match="byte_budget"):
        ConsensusConfig(byte_budget=-1.0)
    with pytest.raises(KeyError):
        C.by_name("fp8")
    with pytest.raises(ValueError, match="k must divide"):
        C.TopKCodec(k=63)
    with pytest.raises(ValueError, match="code_bits"):
        C.SubByteCodec(code_bits=3)


# ---------------------------------------------------------------------------
# int8 refactor: bit-invisible vs the pre-refactor composition
# ---------------------------------------------------------------------------

def test_int8_codec_bit_identical_to_pre_refactor():
    rng, y = _mk(seed=1)
    cd = C.by_name("int8")
    noise = _noise(rng, y.shape[0], cd)
    xt = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    m = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    for step in (None, jnp.float32(1e-2)):
        want = kops.pack_payload(*ref.quantize_blocks_ref(y, noise,
                                                          fixed_step=step))
        for use_pallas in (False, True):
            got = cd.encode_payload(y, noise, fixed_step=step,
                                    use_pallas=use_pallas)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        codes, scales = kops.unpack_payload(want, kops.BLOCK)
        ref_out = ref.dequant_combine_ref(
            codes, scales, codes, scales, codes, scales, xt, m,
            0.5, 0.25, jnp.float32(1.0))
        got_out = cd.decode_combine(want, want, want, xt, m, 0.5, 0.25,
                                    jnp.float32(1.0))
        for a, b in zip(got_out, ref_out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# ref == pallas, whole buffer and chunk views
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_CODECS)
def test_codec_chunk_views_match_monolithic(name):
    """Encode and fused decode-combine chunk views (static row_offset /
    n_rows over full-height operands) == the same rows of the whole-buffer
    launch, bit-for-bit, on both kernel paths — the property the pipelined
    exchange's bit-identity rests on."""
    from repro.core.wire import ChunkedLayout
    cd = C.by_name(name)
    n = 10 * kops.TILE_N
    rng, y = _mk(n=n, seed=2)
    noise = _noise(rng, n, cd)
    xt = jnp.asarray(rng.standard_normal((n, kops.BLOCK)), jnp.float32)
    m = jnp.asarray(rng.standard_normal((n, kops.BLOCK)), jnp.float32)

    class _L:
        n_rows, block = n, kops.BLOCK

    for use_pallas in (False, True):
        for step in (None, jnp.float32(1e-2)):
            full = cd.encode_payload(y, noise, fixed_step=step,
                                     use_pallas=use_pallas)
            dq_full = cd.decode_combine(full, full, full, xt, m, 0.5, 0.25,
                                        jnp.float32(1.0),
                                        use_pallas=use_pallas)
            for k in (2, 7):
                cl = ChunkedLayout.split(_L, k)
                parts = [cd.encode_payload(y, noise, fixed_step=step,
                                           use_pallas=use_pallas,
                                           row_offset=s, n_rows=r)
                         for s, r in cl.bounds]
                np.testing.assert_array_equal(
                    np.asarray(jnp.concatenate(parts)), np.asarray(full))
                dq_parts = [
                    cd.decode_combine(
                        cl.slice_rows(full, c), cl.slice_rows(full, c),
                        cl.slice_rows(full, c), xt, m, 0.5, 0.25,
                        jnp.float32(1.0), use_pallas=use_pallas,
                        row_offset=s, n_rows=r)
                    for c, (s, r) in enumerate(cl.bounds)]
                for i in range(3):
                    np.testing.assert_array_equal(
                        np.asarray(jnp.concatenate(
                            [p[i] for p in dq_parts])),
                        np.asarray(dq_full[i]))


@pytest.mark.parametrize("name", NEW_CODECS)
def test_ref_matches_pallas_bit_for_bit(name):
    cd = C.by_name(name)
    rng, y = _mk(seed=3, spread=3.0)
    noise = _noise(rng, y.shape[0], cd)
    for step in (None, jnp.float32(0.05)):
        a = cd.encode_payload(y, noise, fixed_step=step, use_pallas=False)
        b = cd.encode_payload(y, noise, fixed_step=step, use_pallas=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# unbiasedness: exact rounding probabilities (dense) / selection (top-k)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["int4", "int2"])
def test_dense_rounding_probabilities_exact(name):
    """The sharp unbiasedness instrument: conditioned on the (deterministic,
    adaptive) scale, the code is floor(y/s) + Bernoulli(frac(y/s)).  The
    empirical up-probability must match frac within exact binomial error —
    this catches sub-ulp grid bugs that aggregate-mean Monte Carlo cannot
    (e.g. the bf16 scale-rounding clip bias)."""
    cd = C.by_name(name)
    n, trials = 16, 800
    rng, y = _mk(n=n, seed=4)

    def sample(key):
        noise = jax.random.uniform(key, (n, cd.noise_cols(kops.BLOCK)),
                                   jnp.float32)
        return cd.decode_payload(cd.encode_payload(y, noise))

    keys = jax.random.split(jax.random.PRNGKey(0), trials)
    s = np.asarray(jax.lax.map(jax.jit(sample), keys, batch_size=100),
                   np.float64)
    # scale is deterministic (adaptive mode, y fixed): read it off a payload
    pay0 = cd.encode_payload(y, _noise(rng, n, cd))
    pack = bitpack.subbyte_pack(cd.code_bits)
    scale = np.asarray(bitpack._bf16_bytes_to_scale(
        np.asarray(pay0[:, kops.BLOCK // pack:])), np.float64)
    yy = np.asarray(y, np.float64)
    sratio = yy / scale
    lo = np.floor(sratio)
    frac = sratio - lo
    codes = s / scale                      # exact: scale is a power-of-two-
    up_hat = (codes - lo[None]).mean(0)    # scaled bf16, codes are integers
    # every sample must sit on one of the two adjacent grid points
    assert np.max(np.abs(np.round(s / scale) - s / scale)) < 1e-9
    tol = 5 * np.sqrt(frac * (1 - frac) / trials) + 5.0 / trials
    assert np.max(np.abs(up_hat - frac) - tol) <= 0


def test_topk_unbiasedness_monte_carlo():
    """Three-level check of the sparse codec's unbiasedness: (1) empirical
    selection frequency of every element == |y_i| / sum_stratum(|y| + eps)
    (binomial); (2) conditional on selection, the decoded value ==
    y_i / p_i within the int8 rounding grid; (3) the assembled estimate:
    E[decode(encode(z))] == z, which (1) x (2) imply structurally."""
    cd = C.by_name("topk")
    n, b, trials = 8, kops.BLOCK, 3000
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)

    def sample(key):
        noise = jax.random.uniform(key, (n, cd.noise_cols(b)), jnp.float32)
        return cd.decode_payload(cd.encode_payload(y, noise))

    keys = jax.random.split(jax.random.PRNGKey(1), trials)
    s = np.asarray(jax.lax.map(jax.jit(sample), keys, batch_size=100),
                   np.float64)
    yy = np.asarray(y, np.float64)
    g = b // cd.k
    w = np.abs(yy) + 1e-30
    p = (w.reshape(n, cd.k, g)
         / w.reshape(n, cd.k, g).sum(-1, keepdims=True)).reshape(n, b)
    selected = s != 0.0
    # (1) selection frequencies (y has no exact zeros with this rng)
    p_hat = selected.mean(0)
    tol = 5 * np.sqrt(p * (1 - p) / trials) + 5.0 / trials
    assert np.max(np.abs(p_hat - p) - tol) <= 0
    # (2) conditional value: mean over selected trials == y / p within the
    # rounding noise.  Tolerance = 6 empirical-se + one-grid-step floor for
    # near-deterministic rounding (an up-probability ~1/cnt event that
    # never fired leaves the empirical se at ~0 while the true conditional
    # mean sits a frac * scale away — a statistics artifact, not a bias).
    cnt = selected.sum(0)
    mask = cnt >= 30
    cond_mean = np.where(cnt > 0, s.sum(0) / np.maximum(cnt, 1), 0.0)
    v = yy / p
    row_scale_bound = (np.abs(v).reshape(n, cd.k, g).reshape(n, -1)
                       .max(1) / 127.0 * 1.02)            # (n,)
    import warnings
    with np.errstate(invalid="ignore"), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # all-False columns
        cond_se = np.where(cnt > 0, s.std(0, where=selected)
                           / np.sqrt(np.maximum(cnt, 1)), np.inf)
    floor = row_scale_bound[:, None] * (5.0 / np.maximum(cnt, 1)) + 1e-7
    viol = np.abs(cond_mean - v) - (6 * cond_se + floor)
    assert np.max(viol[mask]) <= 0
    # (3) the assembled estimator over well-sampled elements (elements with
    # p < 20/trials are statistically invisible at this trial count)
    well = p > 20.0 / trials
    agg_se = s.std(0) / np.sqrt(trials) + 1e-12
    bad = np.abs(s.mean(0) - yy) > 6 * agg_se + floor
    assert np.mean(bad[well]) < 0.005


@pytest.mark.parametrize("name", NEW_CODECS)
def test_adaptive_scale_never_clips(name):
    """The bf16 round-UP guarantee: adaptive scales are never below
    absmax / code_max, so no code lands beyond +-code_max and the row max
    element keeps a stochastic (unbiased) rounding."""
    cd = C.by_name(name)
    rng, y = _mk(seed=6, spread=1e4)
    noise = _noise(rng, y.shape[0], cd)
    pay = cd.encode_payload(y, noise)
    if name == "topk":
        wb = kops.BLOCK // 8
        codes = np.asarray(jax.lax.bitcast_convert_type(
            pay[:, wb:wb + cd.k], jnp.int8), np.float64)
    else:
        pack = bitpack.subbyte_pack(cd.code_bits)
        codes = np.asarray(bitpack._unpack_fields(
            pay[:, : kops.BLOCK // pack], cd.code_max, pack))
    assert np.max(np.abs(codes)) <= cd.code_max
    # decode error bounded by one grid step for the dense codecs
    if name != "topk":
        dec = np.asarray(cd.decode_payload(pay))
        pack = bitpack.subbyte_pack(cd.code_bits)
        scale = np.asarray(bitpack._bf16_bytes_to_scale(
            np.asarray(pay[:, kops.BLOCK // pack:])))
        assert np.max(np.abs(dec - np.asarray(y)) / scale) <= 1.0 + 1e-6


def test_bf16_round_is_round_to_nearest_even():
    """The scale rounding is the f32 -> bf16 cast's round-to-nearest-even,
    ties included, and the same inside a jit as op by op (it is integer
    arithmetic, which no compiler may compute at higher precision)."""
    rng = np.random.default_rng(0)
    bits = rng.integers(0x00800000, 0x7F000000, size=4096,
                        dtype=np.int64).astype(np.uint32)
    # exact ties: low half 0x8000, with an even and an odd bf16 mantissa
    bits[:64] = (bits[:64] & 0xFFFE0000) | 0x8000
    bits[64:128] = (bits[64:128] & 0xFFFE0000) | 0x18000
    x = jnp.asarray(bits.view(np.float32))
    want = np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(bitpack._bf16_round(x)), want)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(bitpack._bf16_round)(x)), want)


def test_count_clipped_semantics():
    b = kops.BLOCK
    for name in ALL_CODECS:
        cd = C.by_name(name)
        rng, y = _mk(n=32, seed=7)
        noise = _noise(rng, 32, cd)
        # a fixed step so small everything clips to the boundary
        pay = cd.encode_payload(y, noise, fixed_step=jnp.float32(1e-12))
        clipped = float(cd.count_clipped(pay, b))
        total = 32 * cd.codes_per_row(b)
        assert clipped > 0.9 * total, (name, clipped, total)
        # adaptive payloads: the count must agree with the boundary census
        # of the independently-parsed decode path (cross-checks the payload
        # parsing); for fine grids that census is rare, for int2 (3-level
        # grid) sitting at +-1 is the common case — both are consistent
        pay2 = cd.encode_payload(y, noise)
        clipped2 = float(cd.count_clipped(pay2, b))
        if name == "topk":
            wb = b // 8
            codes = np.asarray(jax.lax.bitcast_convert_type(
                pay2[:, wb:wb + cd.k], jnp.int8), np.float64)
            want = float(np.sum(np.abs(codes) >= cd.code_max))
        else:
            dec = np.asarray(cd.decode_payload(pay2), np.float64)
            if name == "int8":
                scales = np.asarray(kops.unpack_payload(pay2, b)[1],
                                    np.float64)
            else:
                pk = bitpack.subbyte_pack(cd.code_bits)
                scales = np.asarray(bitpack._bf16_bytes_to_scale(
                    np.asarray(pay2[:, b // pk:])), np.float64)
            want = float(np.sum(np.abs(np.round(dec / scales))
                                >= cd.code_max))
        assert clipped2 == want, (name, clipped2, want)
        if name in ("int8", "int4", "topk"):   # fine grids: boundary rare
            assert clipped2 <= total * 0.05


def test_subbyte_saturation_census_from_differential():
    """The overflow metric's signal for coarse grids: count_saturated reads
    |y| > code_max * Delta from the differential, NOT the payload boundary
    census — under int2's 3-level alphabet nearly every legitimate code
    sits at +-1, so the census would cry ~50% overflow on healthy traffic
    and the controller could never hold a sub-byte codec."""
    cd = C.by_name("int2")
    rng, y = _mk(n=32, seed=8)
    noise = _noise(rng, 32, cd)
    # grid wide enough that nothing saturates (|y| <= ~5 sigma < 1 * step)
    step = jnp.float32(8.0)
    pay = cd.encode_payload(y, noise, fixed_step=step)
    census = float(cd.count_clipped(pay))
    sat = float(cd.count_saturated(y, step, pay))
    assert sat == 0.0
    assert census >= 0.0                       # census may count boundary
    # grid far too narrow: everything saturates, both signals agree
    step2 = jnp.float32(1e-6)
    pay2 = cd.encode_payload(y, noise, fixed_step=step2)
    total = y.size
    assert float(cd.count_saturated(y, step2, pay2)) > 0.99 * total
    # exact semantics: |y| > code_max * bf16(step)
    step3 = jnp.float32(1.0)
    want = float(jnp.sum((jnp.abs(y) > cd.code_max
                          * bitpack._bf16_round(step3))
                         .astype(jnp.float32)))
    pay3 = cd.encode_payload(y, noise, fixed_step=step3)
    assert float(cd.count_saturated(y, step3, pay3)) == want
    # adaptive mode (no fixed grid) falls back to the census
    pay4 = cd.encode_payload(y, noise)
    assert float(cd.count_saturated(y, None, pay4)) \
        == float(cd.count_clipped(pay4))
    # fine grids (int8, topk) keep the census as the saturation proxy
    for name in ("int8", "topk"):
        cf = C.by_name(name)
        nz = _noise(rng, 32, cf)
        p = cf.encode_payload(y, nz, fixed_step=jnp.float32(1e-2))
        assert float(cf.count_saturated(y, jnp.float32(1e-2), p)) \
            == float(cf.count_clipped(p))


# ---------------------------------------------------------------------------
# AdaptiveBitController state machine
# ---------------------------------------------------------------------------

def _rows():
    return 640  # any static row count


def test_controller_budget_filter():
    n = _rows()
    ctl = C.AdaptiveBitController(byte_budget=None)
    assert ctl.candidates(n) == ("int2", "int4", "int8")
    int4_bytes = 2 * n * C.by_name("int4").payload_width()
    ctl = C.AdaptiveBitController(byte_budget=int4_bytes)
    assert ctl.candidates(n) == ("int2", "int4")
    # budget below everything: degrade to the cheapest, never empty
    ctl = C.AdaptiveBitController(byte_budget=1.0)
    assert ctl.candidates(n) == ("int2",)
    assert ctl.initial(n) == "int2"


def test_controller_initial_and_fidelity_targeting():
    n = _rows()
    ctl = C.AdaptiveBitController(fixed_step0=0.1, gamma=1.0, headroom=4.0)
    assert ctl.initial(n) == "int8"   # conservative start
    # tiny residual, large grid -> int2 suffices: delta_1 = 0.1,
    # need = rms * 4 / 0.1 = 0.4 <= 1
    assert ctl.target(1, residual_rms=0.01, overflow_frac=0.0,
                      n_rows=n) == "int2"
    # k = 100 -> delta = 1e-3 -> need = 40 > 7: int8
    assert ctl.target(100, residual_rms=0.01, overflow_frac=0.0,
                      n_rows=n) == "int8"
    # k = 10 -> delta = 0.01 -> need = 4 <= 7: int4
    assert ctl.target(10, residual_rms=0.01, overflow_frac=0.0,
                      n_rows=n) == "int4"
    # adaptive quant mode (no fixed grid): budget-cheapest
    assert ctl.target(10, residual_rms=None, overflow_frac=0.0,
                      n_rows=n) == "int2"


def test_controller_hysteresis_and_overflow():
    n = _rows()
    ctl = C.AdaptiveBitController(fixed_step0=0.1, gamma=1.0, patience=2)
    ctl.initial(n)                       # int8
    # down-target must persist `patience` epochs before switching
    assert ctl.select(1, 0.01, 0.0, n) == "int8"    # pending int2 (1)
    assert ctl.select(1, 0.01, 0.0, n) == "int2"    # pending int2 (2) -> go
    # amplification shrinks the grid -> immediate up-switch
    assert ctl.select(100, 0.01, 0.0, n) == "int8"
    # observed clipping forces a rung up even when the prediction says stay
    ctl2 = C.AdaptiveBitController(fixed_step0=0.1, gamma=1.0, patience=1)
    ctl2.initial(n)
    ctl2.select(1, 0.01, 0.0, n)                     # down to int2
    assert ctl2.current == "int2"
    assert ctl2.select(1, 0.01, overflow_frac=0.5, n_rows=n) == "int4"


def test_controller_variance_adaptive_topk_ladder():
    """Variance-adaptive top-k: every rung of a ``topk:k=<int>`` ladder
    shares one grid ceiling (code_max = 127), so raw code_max cannot rank
    them; capacity = code_max * k / block restores the ordering and the
    controller walks k up/down exactly like bit width."""
    n = _rows()
    ks = (16, 32, 64, 128, 256)
    ladder = tuple(f"topk:k={k}" for k in ks)
    # exact pricing: block//8 selection bitmap + k codes + 2 scale rows
    for k in ks:
        assert C.by_name(f"topk:k={k}").payload_width() == \
            kops.BLOCK // 8 + k + 2
    # capacity is strictly increasing in k; dense rungs stay code_max
    caps = [C.AdaptiveBitController._capacity(name) for name in ladder]
    assert caps == sorted(caps) and len(set(caps)) == len(caps)
    assert caps[2] == pytest.approx(127 * 64 / kops.BLOCK)
    for name in ("int2", "int4", "int8"):
        assert C.AdaptiveBitController._capacity(name) == \
            float(C.by_name(name).code_max)
    ctl = C.AdaptiveBitController(ladder=ladder, fixed_step0=1e-3,
                                  gamma=0.0, headroom=4.0, patience=2)
    assert ctl.initial(n) == "topk:k=256"            # conservative start
    # tiny residual: the k=16 down-target persists patience epochs first
    assert ctl.select(1, 1e-5, 0.0, n) == "topk:k=256"
    assert ctl.select(2, 1e-5, 0.0, n) == "topk:k=16"
    # rising residual: immediate up-switch to the cheapest sufficient k
    # (need = 2e-3 * 4 / 1e-3 = 8 -> k=64, capacity 15.9)
    assert ctl.select(3, 2e-3, 0.0, n) == "topk:k=64"
    # need beyond every rung: highest-CAPACITY fallback (not code_max)
    assert ctl.target(4, residual_rms=1.0, overflow_frac=0.0,
                      n_rows=n) == "topk:k=256"
    # observed clipping forces one ladder rung up from the current k
    assert ctl.select(5, 1e-5, overflow_frac=0.5, n_rows=n) == "topk:k=128"
    # the byte-budget filter prices each rung exactly
    budget = 2 * n * C.by_name("topk:k=64").payload_width()
    ctl2 = C.AdaptiveBitController(ladder=ladder, byte_budget=budget)
    assert ctl2.candidates(n) == ladder[:3]
    # candidate_table surfaces the new pricing columns (controller-trace
    # telemetry events)
    row = C.AdaptiveBitController(ladder=ladder).candidate_table(n)[0]
    assert row["coverage"] == pytest.approx(16 / kops.BLOCK)
    assert row["capacity"] == pytest.approx(127 * 16 / kops.BLOCK)


def test_controller_switches_across_amplified_epochs():
    """The acceptance dynamic: with a constant residual and gamma > 0 the
    amplified grid Delta_0 / k^gamma shrinks, so the controller must walk
    up the ladder across epochs (after its conservative int8 start dropped
    to the cheap end)."""
    n = _rows()
    ctl = C.AdaptiveBitController(fixed_step0=0.05, gamma=1.0, patience=1,
                                  headroom=4.0)
    trace = [ctl.initial(n)]
    for epoch, k in enumerate((1, 5, 30, 200, 2000)):
        trace.append(ctl.select(k, residual_rms=0.01, overflow_frac=0.0,
                                n_rows=n))
    assert trace[0] == "int8"
    assert "int2" in trace and "int4" in trace      # walked down then up
    assert trace[-1] == "int8"
    assert len(set(trace)) == 3
