"""Benchmark harness: one benchmark per paper figure/claim + system benches.

Usage:
    PYTHONPATH=src python -m benchmarks.run                 # everything
    PYTHONPATH=src python -m benchmarks.run --only fig5     # one benchmark

Output: ``name,seconds,derived`` CSV lines on stdout plus one JSON artifact
per benchmark under benchmarks/artifacts/ (consumed by EXPERIMENTS.md).

Paper mapping:
  fig1_divergence      — Fig. 1: DGD + direct compression diverges; DGD converges
  fig5_convergence     — Fig. 5: ADC-DGD vs DGD vs DGD^t, constant & diminishing
  fig6_bytes           — Fig. 6: wire bytes vs gradient norm (comm-efficiency)
  fig7_gamma           — Fig. 7: convergence under gamma in {0.6,0.8,1.0,1.2}
  fig8_transmitted     — Fig. 8: growth of max transmitted value vs gamma
  fig10_network_size   — Fig. 10: circle networks n in {3,5,10,20}
  fig10_timevarying    — beyond the paper: ADC-DGD under time-varying
                         topologies (periodic ring/torus, i.i.d. Erdős–Rényi,
                         random-geometric samples)
  choco_vs_adc         — head-to-head vs CHOCO-SGD error-feedback gossip
                         (Koloskova et al. 1902.00340), same compressor
  thm1_consensus       — Thm 1: consensus error, const & diminishing step
  thm2_error_ball      — Thm 2: error ball scales as O(alpha^2)
  thm3_rate            — Thm 3 / Remark 3: o(1/sqrt(k)) rate fit (loglog)
  kernel_quantize      — Pallas quantize kernel vs jnp oracle (exactness + time)
  kernel_dequant       — Pallas dequant+combine kernel vs oracle
  llm_wire_bytes       — int8 ADC wire bytes vs fp32 DGD on the LLM trainer
  roofline_summary     — table from the dry-run artifacts (section Roofline)
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

import numpy as np

ART = os.path.join(os.path.dirname(os.path.abspath(__file__)), "artifacts")


def _save(name: str, payload: dict) -> None:
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)


def _row(name: str, seconds: float, derived: str) -> None:
    print(f"{name},{seconds:.3f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# Paper-figure benchmarks (core.consensus reference algorithms)
# ---------------------------------------------------------------------------

def bench_fig1_divergence() -> None:
    """Fig. 1: 2-node network, f1=4(x-2)^2, f2=2(x+3)^2; direct compression
    fails to converge while plain DGD drives the gradient to ~0."""
    from repro.core import compression, consensus, problems, topology
    t0 = time.time()
    prob = problems.paper_2node()
    mix = topology.fully_connected(2)
    comp = compression.RandomizedRounding(delta=1.0)
    # alpha small enough that DGD's constant-step ball is tiny; the direct
    # compression noise floor then dominates by >10x (the Fig. 1 signature)
    ss = consensus.StepSize(0.005, 0.0)
    steps = 2000
    r_bad = consensus.run(consensus.CompressedDGD(mix, comp, ss), prob, steps, key=0)
    r_dgd = consensus.run(consensus.DGD(mix, ss), prob, steps, key=0)
    r_adc = consensus.run(consensus.ADCDGD(mix, comp, ss, gamma=1.0), prob, steps, key=0)
    tail = slice(-200, None)
    out = {
        "compressed_dgd_tail_gradnorm": float(np.mean(r_bad["grad_norm"][tail])),
        "dgd_tail_gradnorm": float(np.mean(r_dgd["grad_norm"][tail])),
        "adc_tail_gradnorm": float(np.mean(r_adc["grad_norm"][tail])),
        "compressed_dgd_tail_consensus": float(np.mean(r_bad["consensus"][tail])),
        "steps": steps,
    }
    _save("fig1_divergence", out)
    ratio = out["compressed_dgd_tail_gradnorm"] / max(out["dgd_tail_gradnorm"], 1e-30)
    _row("fig1_divergence", time.time() - t0,
         f"direct-compression gradnorm {out['compressed_dgd_tail_gradnorm']:.3g} vs "
         f"dgd {out['dgd_tail_gradnorm']:.3g} ({ratio:.1e}x worse); adc "
         f"{out['adc_tail_gradnorm']:.3g}")


def bench_fig5_convergence() -> None:
    """Fig. 5: four-node network of Section V-1, ADC-DGD/DGD/DGD^3/DGD^5,
    constant (eta=0) and diminishing (eta=1/2) step-sizes."""
    from repro.core import compression, consensus, problems, topology
    t0 = time.time()
    prob = problems.paper_4node()
    mix = topology.paper_fig3()
    comp = compression.RandomizedRounding(delta=1.0)
    steps = 600
    curves = {}
    for eta, tag in ((0.0, "const"), (0.5, "dimin")):
        ss = consensus.StepSize(0.02, eta)  # 0.05 diverges (node-4 L=10)
        algs = {
            "adc_dgd": consensus.ADCDGD(mix, comp, ss, gamma=1.0),
            "dgd": consensus.DGD(mix, ss),
            "dgd_t3": consensus.DGDt(mix, ss, t=3),
            "dgd_t5": consensus.DGDt(mix, ss, t=5),
        }
        for name, alg in algs.items():
            r = consensus.run(alg, prob, steps, key=1)
            curves[f"{name}_{tag}"] = {
                "obj": r["obj"][:: steps // 60].tolist(),
                "final_gradnorm": float(r["grad_norm"][-1]),
            }
    _save("fig5_convergence", {"curves": curves, "steps": steps})
    _row("fig5_convergence", time.time() - t0,
         "final |grad| const: " + " ".join(
             f"{k.rsplit('_', 1)[0]}={v['final_gradnorm']:.2e}"
             for k, v in curves.items() if k.endswith("const")))


def bench_fig6_bytes() -> None:
    """Fig. 6: cumulative wire bytes to reach gradient-norm thresholds.
    ADC-DGD transmits int16-equivalent codes (2B/elem) vs 8B doubles."""
    from repro.core import compression, consensus, problems, topology
    t0 = time.time()
    prob = problems.paper_4node()
    mix = topology.paper_fig3()
    comp = compression.RandomizedRounding(delta=1.0)
    ss = consensus.StepSize(0.02, 0.0)
    steps = 800
    runs = {
        "adc_dgd": consensus.run(consensus.ADCDGD(mix, comp, ss, gamma=1.0), prob, steps, key=2),
        "dgd": consensus.run(consensus.DGD(mix, ss), prob, steps, key=2),
        "dgd_t3": consensus.run(consensus.DGDt(mix, ss, t=3), prob, steps, key=2),
        "dgd_t5": consensus.run(consensus.DGDt(mix, ss, t=5), prob, steps, key=2),
    }
    thresholds = (1e-1, 1e-2)
    table: dict[str, dict[str, float]] = {}
    for name, r in runs.items():
        row = {}
        for th in thresholds:
            idx = int(np.argmax(r["grad_norm"] < th))
            hit = bool(r["grad_norm"][idx] < th)
            row[f"bytes_to_{th:g}"] = float(r["bytes"][idx]) if hit else float("inf")
        table[name] = row
    _save("fig6_bytes", {"table": table, "steps": steps})
    b_adc = table["adc_dgd"]["bytes_to_0.01"]
    b_dgd = table["dgd"]["bytes_to_0.01"]
    _row("fig6_bytes", time.time() - t0,
         f"bytes to |grad|<1e-2: adc={b_adc:.0f} dgd={b_dgd:.0f} "
         f"({b_dgd / max(b_adc, 1):.1f}x saving)")


def bench_fig7_gamma() -> None:
    """Fig. 7: effect of the amplification exponent gamma (100-trial mean)."""
    from repro.core import compression, consensus, problems, topology
    import jax
    t0 = time.time()
    prob = problems.paper_4node()
    mix = topology.paper_fig3()
    comp = compression.RandomizedRounding(delta=1.0)
    ss = consensus.StepSize(0.02, 0.0)
    steps, trials = 400, 100
    out = {}
    for gamma in (0.6, 0.8, 1.0, 1.2):
        alg = consensus.ADCDGD(mix, comp, ss, gamma=gamma)
        traj = consensus.run_many(alg, prob, steps, trials, seed=17)
        mean_obj = np.mean(traj["obj"], axis=0)
        out[f"gamma_{gamma}"] = {
            "obj_tail": float(np.mean(mean_obj[-50:])),
            "obj_curve": mean_obj[:: steps // 50].tolist(),
        }
    _save("fig7_gamma", out)
    _row("fig7_gamma", time.time() - t0,
         " ".join(f"g={g}:{out[f'gamma_{g}']['obj_tail']:.4f}"
                  for g in (0.6, 0.8, 1.0, 1.2)))


def bench_fig8_transmitted() -> None:
    """Fig. 8: max transmitted magnitude growth vs gamma (Prop. 5:
    E||k^g y^k|| = o(k^{g-1/2}) -> slow growth for gamma<=1)."""
    from repro.core import compression, consensus, problems, topology
    from repro.core.theory import fit_loglog_rate
    import jax
    t0 = time.time()
    prob = problems.paper_4node()
    mix = topology.paper_fig3()
    comp = compression.RandomizedRounding(delta=1.0)
    ss = consensus.StepSize(0.02, 0.0)
    steps, trials = 400, 50
    out = {}
    for gamma in (0.6, 0.8, 1.0, 1.2):
        alg = consensus.ADCDGD(mix, comp, ss, gamma=gamma)
        traj = consensus.run_many(alg, prob, steps, trials, seed=23)
        mean_tx = np.mean(traj["max_tx"], axis=0)
        growth = -fit_loglog_rate(np.maximum(mean_tx, 1e-12), 0.5)
        out[f"gamma_{gamma}"] = {"max_tx_final": float(mean_tx[-1]),
                                 "growth_exponent": float(growth),
                                 "prop5_bound": gamma - 0.5}
    _save("fig8_transmitted", out)
    _row("fig8_transmitted", time.time() - t0,
         " ".join(f"g={g}:tx={out[f'gamma_{g}']['max_tx_final']:.2f}"
                  f"(r={out[f'gamma_{g}']['growth_exponent']:+.2f}<{g - 0.5:.1f})"
                  for g in (0.6, 0.8, 1.0, 1.2)))


def bench_fig10_network_size() -> None:
    """Fig. 10: circle networks n in {3,5,10,20}, 100 trials each."""
    from repro.core import compression, consensus, problems, topology
    import jax
    t0 = time.time()
    comp = compression.RandomizedRounding(delta=1.0)
    ss = consensus.StepSize(0.02, 0.0)
    # 20 randomly-drawn problems per size (the paper uses 100; each problem
    # instance retraces the scan, so the bench trades trials for wall time —
    # trial variance at 20 is already < 5% of the mean here)
    steps, trials = 500, 20
    out = {}
    for n in (3, 5, 10, 20):
        mix = topology.paper_circle(n)
        gns = []
        for trial in range(trials):
            prob = problems.paper_circle_problem(n, seed=trial)
            alg = consensus.ADCDGD(mix, comp, ss, gamma=1.0)
            r = consensus.run(alg, prob, steps, key=jax.random.PRNGKey(trial))
            gns.append(r["grad_norm"])
        m = np.mean(np.stack(gns), axis=0)
        out[f"n_{n}"] = {"final_gradnorm": float(m[-1]), "beta": float(mix.beta)}
    _save("fig10_network_size", out)
    _row("fig10_network_size", time.time() - t0,
         " ".join(f"n={n}:|g|={out[f'n_{n}']['final_gradnorm']:.2e}"
                  for n in (3, 5, 10, 20)))


def bench_fig10_timevarying() -> None:
    """Beyond the paper: ADC-DGD on the n=10 circle problem under
    time-varying mixing matrices — periodic ring/torus alternation and
    i.i.d. Erdős–Rényi / random-geometric graph samples (CHOCO-SGD's
    randomized-gossip setting).  The amplified-differential argument only
    needs each W^(k) to satisfy Section III-A, so convergence must match
    the static ring."""
    from repro.core import compression, consensus, problems, topology
    t0 = time.time()
    n = 10
    prob = problems.paper_circle_problem(n, seed=0)
    comp = compression.RandomizedRounding(delta=1.0)
    ss = consensus.StepSize(0.02, 0.5)
    steps = 3000
    # horizon == steps so the random schedules are genuinely i.i.d. draws
    # for the whole run (a shorter horizon would silently cycle)
    schedules = {
        "static_ring": topology.StaticSchedule(topology.ring(n)),
        "ring_torus_alt": topology.PeriodicSchedule(
            [topology.ring(n), topology.torus(2, n // 2)], dwell=5),
        "erdos_renyi": topology.ErdosRenyiSchedule(n, p=0.35, horizon=steps,
                                                   seed=11),
        "rgg": topology.RandomGeometricSchedule(n, radius=0.55, horizon=steps,
                                                seed=13),
    }
    out = {}
    for name, sched in schedules.items():
        alg = consensus.ADCDGD(sched, comp, ss, gamma=1.0)
        r = consensus.run(alg, prob, steps, key=29)
        out[name] = {
            "final_gradnorm": float(np.mean(r["grad_norm"][-100:])),
            "final_consensus": float(np.mean(r["consensus"][-100:])),
            "mean_edges": float(sched.n_edges),
            "beta_mean_matrix": float(sched.beta),
            "max_sample_beta": float(max(m.beta for m in sched.matrices)),
            "total_bytes": float(r["bytes"][-1]),
        }
    _save("fig10_timevarying", {"schedules": out, "steps": steps})
    _row("fig10_timevarying", time.time() - t0,
         " ".join(f"{k}:|g|={v['final_gradnorm']:.1e}"
                  for k, v in out.items()))


def bench_choco_vs_adc() -> None:
    """ADC-DGD vs CHOCO-SGD (error-feedback gossip, arXiv:1902.00340) with
    the SAME unbiased compressor on identical problems — static ring and
    i.i.d. Erdős–Rényi schedule.  Expected: with a constant-variance
    unbiased compressor, CHOCO floors at O(lam*sigma) while ADC-DGD's
    amplification drives the noise to zero; wire bytes are identical."""
    from repro.core import compression, consensus, problems, topology
    t0 = time.time()
    prob = problems.paper_4node()
    comp = compression.RandomizedRounding(delta=1.0)
    ss = consensus.StepSize(0.02, 0.5)
    steps = 4000
    mixes = {
        "ring4": topology.ring(4),
        "er4": topology.ErdosRenyiSchedule(4, p=0.6, horizon=steps, seed=5),
    }
    out = {}
    for mname, mix in mixes.items():
        algs = {
            "adc_dgd": consensus.ADCDGD(mix, comp, ss, gamma=1.0),
            "choco": consensus.CHOCOGossip(mix, comp, ss, consensus_lr=0.3),
            "dgd": consensus.DGD(mix, ss),
        }
        for aname, alg in algs.items():
            r = consensus.run(alg, prob, steps, key=31)
            out[f"{aname}_{mname}"] = {
                "tail_gradnorm": float(np.mean(r["grad_norm"][-200:])),
                "tail_consensus": float(np.mean(r["consensus"][-200:])),
                "total_bytes": float(r["bytes"][-1]),
            }
    _save("choco_vs_adc", {"runs": out, "steps": steps,
                           "consensus_lr": 0.3, "delta": 1.0})
    g = {k: v["tail_gradnorm"] for k, v in out.items()}
    _row("choco_vs_adc", time.time() - t0,
         f"ring4 |g|: adc={g['adc_dgd_ring4']:.1e} "
         f"choco={g['choco_ring4']:.1e} dgd={g['dgd_ring4']:.1e}; "
         f"er4: adc={g['adc_dgd_er4']:.1e} choco={g['choco_er4']:.1e}")


def bench_thm1_consensus() -> None:
    """Theorem 1: consensus error bounded by alpha*D/(1-beta) + O(1/k^g)
    (constant step) and -> 0 (diminishing step)."""
    from repro.core import compression, consensus, problems, topology
    t0 = time.time()
    prob = problems.paper_4node()
    mix = topology.paper_fig3()
    comp = compression.RandomizedRounding(delta=0.5)
    steps = 2000
    r_const = consensus.run(
        consensus.ADCDGD(mix, comp, consensus.StepSize(0.02, 0.0), gamma=1.0),
        prob, steps, key=3)
    r_dimin = consensus.run(
        consensus.ADCDGD(mix, comp, consensus.StepSize(0.02, 0.5), gamma=1.0),
        prob, steps, key=3)
    out = {
        "const_tail_consensus": float(np.mean(r_const["consensus"][-200:])),
        "dimin_tail_consensus": float(np.mean(r_dimin["consensus"][-200:])),
        "dimin_mid_consensus": float(np.mean(r_dimin["consensus"][200:400])),
        "beta": float(mix.beta),
    }
    _save("thm1_consensus", out)
    _row("thm1_consensus", time.time() - t0,
         f"const err={out['const_tail_consensus']:.2e} (bounded), dimin "
         f"{out['dimin_mid_consensus']:.2e}->{out['dimin_tail_consensus']:.2e} (down)")


def bench_thm2_error_ball() -> None:
    """Theorems 1/2 error-ball scaling in the constant step-size alpha.

    Two measurements, long horizon (compression noise ~1/k^2g fully decayed):
      * consensus ball ||x - xbar||     — Thm 1 bound alpha*D/(1-beta):
        LINEAR in alpha, coefficient never cancels => ratio ~2 per doubling.
      * gradient ball ||mean grad||^2   — Thm 2 bound O(alpha^2): an UPPER
        bound only; on the paper's 4-node problem the leading bias
        coefficient crosses zero between alpha=0.01 and 0.02 (verified
        against the analytic DGD fixed point), so we check bound
        satisfaction, not tightness.
    """
    from repro.core import compression, consensus, problems, topology
    t0 = time.time()
    prob = problems.paper_4node()
    mix = topology.paper_fig3()
    comp = compression.RandomizedRounding(delta=0.2)
    steps = 8000
    cons, grads = {}, {}
    for alpha in (0.005, 0.01, 0.02):
        r = consensus.run(
            consensus.ADCDGD(mix, comp, consensus.StepSize(alpha, 0.0), gamma=1.0),
            prob, steps, key=4)
        cons[alpha] = float(np.mean(r["consensus"][-800:]))
        grads[alpha] = float(np.mean(r["grad_norm"][-800:] ** 2))
    alphas = sorted(cons)
    c_ratios = [cons[alphas[i + 1]] / max(cons[alphas[i]], 1e-30)
                for i in range(len(alphas) - 1)]
    # Thm 2 bound constant estimated from the largest alpha (L~10, beta<1)
    bound_c = max(grads[a] / a**2 for a in alphas)
    bound_ok = all(grads[a] <= bound_c * a**2 * 1.0001 for a in alphas)
    _save("thm2_error_ball", {
        "consensus_ball": {str(a): cons[a] for a in alphas},
        "consensus_doubling_ratios": c_ratios,
        "grad_ball": {str(a): grads[a] for a in alphas},
        "grad_bound_constant": bound_c, "grad_bound_satisfied": bound_ok})
    _row("thm2_error_ball", time.time() - t0,
         "consensus ball: " + " ".join(f"{a}:{cons[a]:.2e}" for a in alphas) +
         f" ratios={['%.2f' % r for r in c_ratios]} (theory 2.0); "
         f"grad ball <= {bound_c:.2g}*alpha^2: {bound_ok}")


def bench_thm3_rate() -> None:
    """Theorem 3 / Remark 3: diminishing alpha_k = a/sqrt(k), gamma>1/2 ->
    ||grad||^2 decays o(1/sqrt(k)); log-log rate fit should be >= ~0.5.
    Also: ADC-DGD's fitted rate matches uncompressed DGD (headline claim)."""
    from repro.core import compression, consensus, problems, theory, topology
    t0 = time.time()
    prob = problems.paper_4node()
    mix = topology.paper_fig3()
    comp = compression.RandomizedRounding(delta=0.5)
    ss = consensus.StepSize(0.02, 0.5)
    steps = 4000
    r_adc = consensus.run(consensus.ADCDGD(mix, comp, ss, gamma=1.0), prob, steps, key=5)
    r_dgd = consensus.run(consensus.DGD(mix, ss), prob, steps, key=5)
    def floor_aware_rate(g2):
        # fit only while above numerical floor (DGD reaches ~1e-12 fast)
        above = g2 > 1e-8
        last = int(np.argmin(above)) if not above.all() else len(g2)
        last = max(last, len(g2) // 4)
        return theory.fit_loglog_rate(g2[:last], 0.3)
    rate_adc = floor_aware_rate(r_adc["grad_norm"] ** 2)
    rate_dgd = floor_aware_rate(r_dgd["grad_norm"] ** 2)
    _save("thm3_rate", {"rate_adc": rate_adc, "rate_dgd": rate_dgd,
                        "theory_min": 0.5})
    _row("thm3_rate", time.time() - t0,
         f"||grad||^2 decay exponents: adc={rate_adc:.2f} dgd={rate_dgd:.2f} "
         f"(theory >= 0.5; match => compression is free)")


# ---------------------------------------------------------------------------
# Kernel + LLM-system benches
# ---------------------------------------------------------------------------

def _time_jit(fn, *args, iters: int = 5) -> float:
    import jax
    out = fn(*args)
    jax.tree.map(lambda a: a.block_until_ready(), out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.tree.map(lambda a: a.block_until_ready(), out)
    return (time.time() - t0) / iters


def bench_kernel_quantize() -> None:
    """Pallas (interpret) quantize kernel vs jnp oracle: bit-exactness and
    CPU wall time (interpret mode is a correctness artifact, not TPU perf)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    t0 = time.time()
    rows, blk = 256, ops.BLOCK
    y = jax.random.normal(jax.random.PRNGKey(0), (rows, blk), jnp.float32)
    noise = jax.random.uniform(jax.random.PRNGKey(1), (rows, blk), jnp.float32)
    c_p, s_p = ops.quantize_blocks(y, noise, use_pallas=True)
    c_r, s_r = ref.quantize_blocks_ref(y, noise)
    exact = bool(jnp.all(c_p == c_r)) and bool(jnp.all(s_p == s_r))
    t_ref = _time_jit(jax.jit(lambda a, b: ref.quantize_blocks_ref(a, b)), y, noise)
    _save("kernel_quantize", {"bit_exact": exact, "rows": rows, "block": blk,
                              "ref_us": t_ref * 1e6})
    _row("kernel_quantize", time.time() - t0,
         f"pallas==oracle:{exact} ({rows}x{blk}), jnp path {t_ref * 1e6:.0f}us")


def bench_kernel_dequant() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    t0 = time.time()
    rows, blk = 256, ops.BLOCK
    k = jax.random.PRNGKey(0)
    y = jax.random.normal(k, (rows, blk), jnp.float32)
    noise = jax.random.uniform(k, (rows, blk), jnp.float32)
    codes, scales = ref.quantize_blocks_ref(y, noise)
    args = (codes, scales, codes, scales, codes, scales, y, 0.5 * y,
            0.5, 0.25, jnp.float32(1.0))
    outs_p = ops.dequant_combine(*args, use_pallas=True)
    outs_r = ref.dequant_combine_ref(*args)
    exact = all(bool(jnp.all(a == b)) for a, b in zip(outs_p, outs_r))
    t_ref = _time_jit(jax.jit(ref.dequant_combine_ref), *args)
    _save("kernel_dequant", {"bit_exact": exact, "ref_us": t_ref * 1e6})
    _row("kernel_dequant", time.time() - t0,
         f"pallas==oracle:{exact}, jnp path {t_ref * 1e6:.0f}us")


def bench_kernel_gqa_decode() -> None:
    """Flash-decode GQA kernel vs oracle: combined-output equivalence over a
    32k cache shard + jnp path timing."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    t0 = time.time()
    b, kvh, g, hd, S = 4, 8, 4, 128, 4096
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, kvh, g, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, S, kvh, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, S, kvh, hd), jnp.bfloat16)
    valid = jnp.arange(S) < S - 5
    mp, lp, ap = ops.gqa_decode(q, k, v, valid, use_pallas=True)
    mr, lr, ar = ref.gqa_decode_ref(q, k, v, valid)
    outp = np.asarray(ap) / np.asarray(lp)[..., None]
    outr = np.asarray(ar) / np.asarray(lr)[..., None]
    err = float(np.max(np.abs(outp - outr)))
    t_ref = _time_jit(jax.jit(lambda *a: ref.gqa_decode_ref(*a)), q, k, v, valid)
    _save("kernel_gqa_decode", {"max_out_err": err, "S": S,
                                "ref_us": t_ref * 1e6})
    _row("kernel_gqa_decode", time.time() - t0,
         f"pallas-vs-oracle out err {err:.1e} over S={S} cache, "
         f"jnp path {t_ref * 1e6:.0f}us")


def bench_llm_wire_bytes() -> None:
    """Wire traffic per training step on the LLM trainer: ADC int8 payload
    vs DGD fp32, bytes AND ring collectives, straight from the runtime's
    static accounting (ConsensusRuntime.wire_bytes_per_step /
    .collectives_per_step — no hand-derived constants)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.distributed import ConsensusConfig, ConsensusRuntime
    from repro.models import transformer as T
    from repro.models.params import ParamDef, local_block_shape
    from repro.models.sharding import ParallelContext
    t0 = time.time()
    out = {}
    for arch in ("smollm-135m", "yi-9b", "jamba-v0.1-52b"):
        cfg = get_config(arch)
        n_params = cfg.param_count()
        # production mesh: params sharded over 16 fsdp x 16 tp per pod
        ctx = ParallelContext(tp=16, data_size=64, n_nodes=4)
        defs = T.build_defs(cfg, ctx)
        leaves = jax.tree_util.tree_flatten(
            defs.storage, is_leaf=lambda x: isinstance(x, ParamDef))[0]
        local = [jax.ShapeDtypeStruct(
            local_block_shape(d, ctx.tp, ctx.fsdp), d.dtype)
            for d in leaves]
        from repro.core import wire
        layout = wire.WireLayout.for_tree(local)
        adc = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd"), ctx)
        adc_pl = ConsensusRuntime(ConsensusConfig(
            algorithm="adc_dgd", wire_packing="per_leaf"), ctx)
        dgd = ConsensusRuntime(ConsensusConfig(algorithm="dgd",
                                               wire_dtype=jnp.float32), ctx)
        b_adc = adc.wire_bytes_per_step(layout.n_elements, layout=layout)
        b_dgd = dgd.wire_bytes_per_step(layout.n_elements)
        out[arch] = {
            "params": n_params, "leaves": layout.n_leaves,
            "local_params": layout.n_elements,
            "adc_bytes_per_dev": b_adc, "dgd_fp32_bytes_per_dev": b_dgd,
            "compression_x": b_dgd / b_adc,
            "adc_collectives": adc.collectives_per_step(layout.n_leaves),
            "adc_per_leaf_collectives":
                adc_pl.collectives_per_step(layout.n_leaves),
            "dgd_collectives": dgd.collectives_per_step(layout.n_leaves),
        }
    _save("llm_wire_bytes", out)
    _row("llm_wire_bytes", time.time() - t0,
         " ".join(f"{a}:{v['compression_x']:.2f}x,"
                  f"{int(v['adc_per_leaf_collectives'])}->"
                  f"{int(v['adc_collectives'])}coll"
                  for a, v in out.items()))


def bench_consensus_step_latency() -> None:
    """Per-leaf vs packed vs pipelined consensus exchange on real LLM leaf
    trees (see benchmarks/consensus_step.py).  Runs in a subprocess on four
    forced host CPU devices (``JAX_PLATFORMS=cpu``), so it never competes
    with this process for an accelerator; fails (raises) on any smoke gate:
    packed slower than per-leaf, pipelined best-chunk slower than packed,
    or packed compile time over its trace-size budget."""
    import subprocess
    import sys
    t0 = time.time()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run([sys.executable, "-m", "benchmarks.consensus_step"],
                          capture_output=True, text=True, cwd=repo, env=env,
                          timeout=3000)
    if proc.returncode != 0:
        raise RuntimeError(f"consensus_step failed:\n{proc.stdout[-2000:]}\n"
                           f"{proc.stderr[-2000:]}")
    with open(os.path.join(repo, "BENCH_consensus_step.json")) as f:
        series = json.load(f)
    runs = (series["runs"] if isinstance(series.get("runs"), list)
            else [{"payload": series}])   # pre-series single-payload file
    # the WHOLE append-mode series, sha-ordered (append order): a
    # trajectory row per run with its gates_ok verdict — not just the
    # newest payload
    import sys as _sys
    _sys.path.insert(0, os.path.join(repo, "src"))
    from repro.launch.obs import series_rows
    print(f"  consensus_step series: {len(runs)} run(s)")
    failed = []
    for i, run in enumerate(runs):
        rows = series_rows(run.get("payload") or {})
        sps = sorted(r["steps_per_s"] for r in rows.values()
                     if r.get("steps_per_s"))
        med = sps[len(sps) // 2] if sps else float("nan")
        gates = run.get("gates_ok")
        if gates is False:
            failed.append(i)
        print(f"    run {i}: sha={(run.get('git_sha') or '-')[:8]} "
              f"config={(run.get('config_hash') or '-')[:12]} "
              f"gates={'-' if gates is None else ('ok' if gates else 'FAIL')} "
              f"median {med:.2f} steps/s over {len(rows)} timings")
    payload = runs[-1]["payload"]
    derived = " ".join(
        f"{a}:{v['speedup']:.1f}x({int(v['per_leaf']['collectives_per_step'])}"
        f"->{int(v['packed']['collectives_per_step'])}coll,"
        f"pipe{v['pipelined_vs_packed']:.2f}x@c{v['pipelined']['best_chunks']})"
        for a, v in payload["archs"].items())
    ov = payload.get("overlap")
    if ov:
        derived += (f" async_ovh:"
                    f"{ov['modes']['async']['consensus_overhead_frac']:.0%}")
    if failed:
        raise RuntimeError(
            f"bench-series gate regression: run(s) {failed} of "
            f"BENCH_consensus_step.json have gates_ok=false")
    _row("consensus_step_latency", time.time() - t0, derived)


def bench_roofline_summary() -> None:
    """Collate the dry-run artifacts into the section-Roofline table."""
    t0 = time.time()
    d = os.path.join(ART, "dryrun")
    rows = []
    if os.path.isdir(d):
        for fn in sorted(os.listdir(d)):
            if not fn.endswith(".json"):
                continue
            r = json.load(open(os.path.join(d, fn)))
            if r.get("skipped") or r.get("mesh") != "pod16x16":
                continue
            canonical = (f"{r['arch']}__{r['shape']}__{r['mesh']}__"
                         f"{r.get('variant', 'adc_int8')}.json")
            if fn != canonical:
                continue  # tagged section-Perf experiment variants
            rows.append({k: r[k] for k in (
                "arch", "shape", "chips", "compute_s", "memory_s",
                "collective_s", "dominant", "useful_flops_ratio")}
                | {"variant": r.get("variant", "adc_int8")})
    # wire columns from the runtime's static accounting (written by
    # llm_wire_bytes; collectives/bytes per step, packed vs per-leaf) —
    # the roofline reports the packed-wire reduction without hand-derived
    # constants.
    wire_path = os.path.join(ART, "llm_wire_bytes.json")
    wire_cols = json.load(open(wire_path)) if os.path.exists(wire_path) else {}
    _save("roofline_summary", {"rows": rows, "wire": wire_cols})
    doms: dict[str, int] = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    _row("roofline_summary", time.time() - t0,
         f"{len(rows)} single-pod combos; dominant terms: {doms}")


BENCHES = {
    "fig1": bench_fig1_divergence,
    "fig5": bench_fig5_convergence,
    "fig6": bench_fig6_bytes,
    "fig7": bench_fig7_gamma,
    "fig8": bench_fig8_transmitted,
    "fig10": bench_fig10_network_size,
    "fig10_timevarying": bench_fig10_timevarying,
    "choco_vs_adc": bench_choco_vs_adc,
    "thm1": bench_thm1_consensus,
    "thm2": bench_thm2_error_ball,
    "thm3": bench_thm3_rate,
    "kernel_quantize": bench_kernel_quantize,
    "kernel_dequant": bench_kernel_dequant,
    "kernel_gqa_decode": bench_kernel_gqa_decode,
    "llm_wire_bytes": bench_llm_wire_bytes,
    "consensus_step_latency": bench_consensus_step_latency,
    "roofline": bench_roofline_summary,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench keys")
    args = ap.parse_args()
    keys = args.only.split(",") if args.only else list(BENCHES)
    print("name,seconds,derived")
    for k in keys:
        BENCHES[k]()


if __name__ == "__main__":
    main()
