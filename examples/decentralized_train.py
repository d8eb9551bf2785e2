"""End-to-end decentralized LM training with ADC-DGD gradient consensus.

The production story: data-parallel training where the gradient
synchronization between consensus nodes goes over SLOW links, so the
parameter exchanges are int8-compressed amplified differentials (the
paper's Algorithm 2) instead of fp32 all-reduce.

This driver runs on the CPU, with 8 host devices emulating the mesh: 4
data rows x 2 model columns, 2 consensus nodes x 2-way FSDP.  It sets
JAX_PLATFORMS=cpu itself, so on an accelerator host it still runs on the
host devices (``chip_smoke.py`` drives the train step on a TPU).
It trains a reduced SmolLM-family model for a few hundred steps and
compares against uncompressed DGD and classic all-reduce, reporting loss,
consensus error and wire bytes.

Run:
    PYTHONPATH=src python examples/decentralized_train.py            # quick
    PYTHONPATH=src python examples/decentralized_train.py --steps 300
    PYTHONPATH=src python examples/decentralized_train.py --arch qwen3-0.6b
"""
import os

# the 8-device mesh is made of host CPU devices
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1.0)
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--full-size", action="store_true",
                    help="train the FULL config (slow on CPU) instead of the "
                         "reduced smoke variant")
    args = ap.parse_args()

    from repro.configs import get_config, reduced
    from repro.data import SyntheticLMDataset
    from repro.launch import train as LT
    from repro.launch.mesh import make_cpu_mesh

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = reduced(cfg)
    mesh = make_cpu_mesh(data=4, model=2)
    print(f"arch={cfg.arch_id}  params={cfg.param_count() / 1e6:.1f}M  "
          f"mesh=(data=4, model=2)  consensus nodes=2 (x2-way FSDP)")

    ds_kw = {}
    if cfg.frontend == "audio_frames":
        ds_kw = dict(enc_frames=cfg.encoder_frames, d_model=cfg.d_model)
    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch, n_shards=4,
                            **ds_kw)

    results = {}
    for alg, kw in (("adc_dgd", dict(quant_mode="adaptive", gamma=args.gamma)),
                    ("dgd", {}),
                    ("allreduce", {})):
        setup = LT.build_train_setup(
            cfg, mesh, consensus_nodes=2, algorithm=alg, lr=args.lr,
            global_batch=args.batch,
            track_consensus_error=(alg != "allreduce"), **kw)
        state = LT.init_train_state(setup, jax.random.PRNGKey(0))
        n_local = max(leaf.size for leaf in jax.tree.leaves(state["params"]))
        wire = setup.consensus.wire_bytes_per_step(
            sum(leaf.size for leaf in jax.tree.leaves(state["params"])) // 8)
        losses, cerr = [], []
        t0 = time.time()
        for step in range(args.steps):
            batch = jax.device_put(ds.global_batch_arrays(step),
                                   setup.batch_sharding)
            state, m = setup.train_step(state, batch)
            losses.append(float(m["loss"]))
            if "consensus_err" in m:
                cerr.append(float(m["consensus_err"]))
            if step % max(1, args.steps // 6) == 0:
                extra = f" cerr={cerr[-1]:.3f}" if cerr else ""
                print(f"  [{alg:>9}] step {step:4d} loss={losses[-1]:.4f}{extra}")
        dt = time.time() - t0
        results[alg] = dict(losses=losses, cerr=cerr, wire=wire, dt=dt)
        print(f"  [{alg:>9}] done in {dt:.1f}s "
              f"({dt / args.steps * 1e3:.0f} ms/step), "
              f"wire bytes/step/device={wire:,.0f}")

    print("\nsummary (mean of last 10 losses):")
    for alg, r in results.items():
        tail = float(np.mean(r["losses"][-10:]))
        print(f"  {alg:>9}: loss={tail:.4f}  wire/step/dev={r['wire']:>12,.0f} B"
              + (f"  consensus_err={r['cerr'][-1]:.4f}" if r["cerr"] else ""))
    adc, dgd = results["adc_dgd"], results["dgd"]
    if dgd["wire"]:
        print(f"\nADC-DGD transmits {dgd['wire'] / adc['wire']:.2f}x fewer "
              f"bytes than uncompressed DGD while tracking its loss within "
              f"{abs(np.mean(adc['losses'][-10:]) - np.mean(dgd['losses'][-10:])):.3f}.")


if __name__ == "__main__":
    main()
