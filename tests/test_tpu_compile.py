"""TPU compiles against a described v5e:2x2 topology (no chip attached).

The TPU compiler is installed here and compiles for a chip that is only
described, so these tests catch what interpret mode cannot: kernels Mosaic
refuses to lower, and a sharded train step that does not compile for four
chips.  They prove nothing about results or times.

Every wire kernel is compiled at smollm-135m's one-chip packed width, the
flash-attention kernels at the attention shapes of both benchmark cells,
and the four-chip ADC-DGD train step on a mesh of the described devices.
The topology is described inside a fixture (only one process may load the
TPU library at a time), and the persistent compilation cache stays off in
this file: an entry compiled for a described chip cannot be read back
here.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import bitpack, ops
from repro.kernels import flash_attention as FA
from repro.kernels.dequant_combine import dequant_combine_payload_pallas
from repro.kernels.quantize import BLOCK, SCALE_BYTES, quantize_payload_pallas
from repro.launch import train as LT
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.sharding import ParallelContext

#: quantization-block rows of one smollm-135m replica's packed buffer
SMOLLM_ROWS = 262_752


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_smollm_packed_width():
    """The width the kernel compiles use is the trainer's real one."""
    ctx = ParallelContext(tp=1, data_size=1, n_nodes=1, in_shard_map=True)
    defs = T.build_defs(get_config("smollm-135m"), ctx)
    assert LT.consensus_wire_layout(defs, ctx).n_rows == SMOLLM_ROWS


def _encode(name):
    """(kernel fn, noise columns, payload width) of one encoder."""
    if name.startswith("int8"):
        fixed = name == "int8_fixed"
        return ((lambda y, u, s: quantize_payload_pallas(
            y, u, fixed_step=s if fixed else None, interpret=False)),
            BLOCK, BLOCK + SCALE_BYTES)
    if name.startswith("topk"):
        k = int(name.split("_")[1])
        return ((lambda y, u, s: bitpack.topk_encode_pallas(
            y, u, k, fixed_step=s, interpret=False)),
            2 * BLOCK, bitpack.topk_payload_width(BLOCK, k))
    bits = int(name[3])
    return ((lambda y, u, s: bitpack.subbyte_encode_pallas(
        y, u, bits, fixed_step=s, interpret=False)),
        BLOCK, bitpack.subbyte_payload_width(BLOCK, bits))


@pytest.mark.parametrize("name", ["int8_fixed", "int8_adaptive", "int4",
                                  "int2", "topk_16", "topk_64", "topk_256"])
def test_encode_kernel_compiles(one_chip, name):
    fn, noise_cols, _ = _encode(name)
    y = jax.ShapeDtypeStruct((SMOLLM_ROWS, BLOCK), jnp.float32,
                             sharding=one_chip)
    u = jax.ShapeDtypeStruct((SMOLLM_ROWS, noise_cols), jnp.float32,
                             sharding=one_chip)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _hlo(fn, y, u, s)


@pytest.mark.parametrize("name", ["int8", "int4", "int2", "topk_64"])
def test_combine_kernel_compiles(one_chip, name):
    if name == "int8":
        width = BLOCK + SCALE_BYTES

        def fn(ps, pl, pr, xt, m):
            return dequant_combine_payload_pallas(
                ps, pl, pr, xt, m, 0.5, 0.25, 1.0, interpret=False)
    elif name.startswith("topk"):
        width = bitpack.topk_payload_width(BLOCK, 64)

        def fn(ps, pl, pr, xt, m):
            return bitpack.topk_combine_pallas(
                ps, pl, pr, xt, m, 0.5, 0.25, 1.0, 64, interpret=False)
    else:
        bits = int(name[3])
        width = bitpack.subbyte_payload_width(BLOCK, bits)

        def fn(ps, pl, pr, xt, m):
            return bitpack.subbyte_combine_pallas(
                ps, pl, pr, xt, m, 0.5, 0.25, 1.0, bits, interpret=False)
    pay = jax.ShapeDtypeStruct((SMOLLM_ROWS, width), jnp.uint8,
                               sharding=one_chip)
    buf = jax.ShapeDtypeStruct((SMOLLM_ROWS, BLOCK), jnp.float32,
                               sharding=one_chip)
    assert "tpu_custom_call" in _hlo(fn, pay, pay, pay, buf, buf)


def test_four_chip_adc_train_step_compiles(topo, monkeypatch):
    """Full-width smollm-135m, four nodes on four chips (data=4): the
    compiled ADC step holds the Pallas wire kernels and exactly the two
    ring collective-permutes of the packed exchange."""
    # the wrappers must take their compiled-kernel branch although the
    # process's backend is the CPU
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    setup = LT.build_train_setup(get_config("smollm-135m"), mesh,
                                 consensus_nodes=4, algorithm="adc_dgd",
                                 use_pallas=True, global_batch=32)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        setup.state_shape, setup.state_sharding)
    batch = {k: jax.ShapeDtypeStruct((32, 2048), jnp.int32, sharding=sh)
             for k, sh in setup.batch_sharding.items()}
    text = setup.train_step.lower(state, batch).compile().as_text()
    assert "tpu_custom_call" in text
    permutes = re.findall(r" collective-permute(?:-start)?\(", text)
    assert len(permutes) == 2, permutes


@pytest.mark.parametrize("b,s,kvh,g,hd", [(8, 2048, 3, 3, 64),
                                          (1, 4096, 8, 2, 128)],
                         ids=["smollm-135m", "qwen3-0.6b"])
def test_flash_attention_compiles(one_chip, monkeypatch, b, s, kvh, g, hd):
    """A layer's attention core at the cells' shapes, differentiated under
    the layer's full remat, compiles to the named kernels: the forward for
    the forward pass and again for the recompute, dq and dkv once, each
    under the ``attention/core`` scope the trace reduction reads."""
    monkeypatch.setattr(L, "default_interpret", lambda: False)
    monkeypatch.setattr(FA, "default_interpret", lambda: False)

    def loss(q, k, v):
        with jax.named_scope("attention"):
            return jnp.sum(L.attention_core(q, k, v) ** 2)

    shapes = [(b, s, kvh, g, hd), (b, s, kvh, hd), (b, s, kvh, hd)]
    args = [jax.ShapeDtypeStruct(x, jnp.float32, sharding=one_chip)
            for x in shapes]
    text = _hlo(jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1, 2)),
                *args)
    calls = re.findall(r"%(flash_attn_\w+)\.\d+ = .*tpu_custom_call.*"
                       r"op_name=\"([^\"]*)\"", text)
    assert sorted(n for n, _ in calls) == [
        "flash_attn_dkv", "flash_attn_dq", "flash_attn_fwd", "flash_attn_fwd"]
    # jvp(attention)/core/... in the forward, .../attention/core/... else
    assert all(re.search(r"attention\)*/core/flash_attn_", path)
               for _, path in calls), calls
