"""Pallas TPU kernels for the consensus wire path + jnp oracles.

  quantize.py         stochastic int8 block quantizer (+ fused payload
                      emitter for the packed wire)
  dequant_combine.py  fused decode + shadow update + ring combine
  bitpack.py          sub-byte (int4/int2) bit-packed and top-k sparse
                      wire codecs (DESIGN.md §Wire codecs)
  gqa_decode.py       flash-decode GQA partials over sharded KV caches
  flash_attention.py  causal flash attention for the train step (forward,
                      dq and dkv kernels behind one custom_vjp)
  ops.py              jit'd dispatch wrappers (pallas vs jnp reference)
  ref.py              pure-jnp oracles (bit-exact vs interpret kernels)
"""
