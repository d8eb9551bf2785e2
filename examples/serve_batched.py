"""Batched serving: prefill + greedy decode with a sharded KV/SSM cache.

Serves a reduced model on an 8-device mesh of host CPU devices (2 data x
4 model; the script sets JAX_PLATFORMS=cpu itself, so it runs on the host
devices on an accelerator host too):
  1. prefill a batch of prompts (builds the sharded decode cache),
  2. decode N tokens autoregressively with single-token serve steps.

Works for attention archs (sharded KV cache), SSM archs (recurrent state;
try --arch mamba2-1.3b) and hybrids (--arch jamba-v0.1-52b).

Run:
    PYTHONPATH=src python examples/serve_batched.py
    PYTHONPATH=src python examples/serve_batched.py --arch mamba2-1.3b
"""
import os

# the 8-device mesh is made of host CPU devices
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_cpu_mesh
    from repro.launch.serve import build_prefill_setup, build_serve_setup
    from repro.models.params import materialize_storage_host

    cfg = reduced(get_config(args.arch))
    mesh = make_cpu_mesh(data=2, model=4)
    capacity = args.prompt_len + args.new_tokens

    print(f"arch={cfg.arch_id} mesh=(data=2, model=4) batch={args.batch} "
          f"prompt={args.prompt_len} +{args.new_tokens} tokens")

    # --- params (one replica; serving has no consensus nodes) -------------
    pre = build_prefill_setup(cfg, mesh, global_batch=args.batch,
                              seq_len=args.prompt_len)
    host_params = materialize_storage_host(
        pre.defs.storage, jax.random.PRNGKey(0), pre.ctx.tp, 1, pre.ctx.fsdp)
    params = jax.device_put(jax.tree.map(jnp.asarray, host_params),
                            pre.params_sharding)

    # --- prefill -----------------------------------------------------------
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len),
                           dtype=np.int32)
    batch = {"tokens": jnp.asarray(prompts)}
    if cfg.frontend == "audio_frames":
        batch["enc_frames"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.encoder_frames, cfg.d_model))
            .astype(np.float32))
    t0 = time.time()
    first_ids, cache = pre.prefill_step(params, batch)
    first_ids.block_until_ready()
    print(f"prefill: {time.time() - t0:.2f}s -> first tokens "
          f"{np.asarray(first_ids)[:, 0].tolist()}")

    # --- decode ------------------------------------------------------------
    serve = build_serve_setup(cfg, mesh, global_batch=args.batch,
                              capacity=capacity)
    # place the prefill cache into the serve state (same specs family);
    # cache shapes: prefill built prompt-len entries, serve wants capacity —
    # pad the sequence dim up to capacity.
    def pad_to_cap(pref, srv):
        pads = [(0, s - p) for p, s in zip(pref.shape, srv.shape)]
        return jnp.pad(pref, pads)

    cache_shape = serve.state_shape["cache"]
    cache = jax.tree.map(
        lambda p, s: pad_to_cap(p, s) if p.shape != s.shape else p,
        cache, cache_shape,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))
    state = jax.device_put(
        {"params": params, "cache": cache, "tokens": first_ids},
        serve.state_sharding)

    out_tokens = [np.asarray(first_ids)[:, 0]]
    t0 = time.time()
    for _ in range(args.new_tokens - 1):
        state = serve.serve_step(state)
        out_tokens.append(np.asarray(state["tokens"])[:, 0])
    dt = time.time() - t0
    gen = np.stack(out_tokens, axis=1)
    print(f"decode: {args.new_tokens - 1} steps in {dt:.2f}s "
          f"({dt / max(args.new_tokens - 1, 1) * 1e3:.0f} ms/token/batch)")
    for b in range(args.batch):
        print(f"  seq {b}: {gen[b].tolist()}")
    assert not np.isnan(gen).any()
    print("ok: batched serve produced tokens on the sharded cache")


if __name__ == "__main__":
    main()
