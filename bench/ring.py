"""Plain reference of a ring of nodes that train their own replicas and
mix them with ADC-DGD, as a traffic file states it (Nassif et al.'s
amplified differential compression over a ring).

Shared by every reference module: the module gives the model
(``Model.from_config``, ``init_params``, ``grad_fn``, ``adam``); this file
gives the nodes and the exchange.  It imports nothing of the program
under test.  Every node starts from the same weights (``init_params`` of
the seed), trains on its own rows of each step with Adam, and then mixes,
at step k = 1, 2, ...:

    y   = x - x~                          (x: the node before its step)
    D   = fixed_step0 / k^gamma
    c   = clip(floor(y / D) + [u < frac(y / D)], -127, 127)   int8 codes
    q   = c D
    x~ += q;  m += w_side (q_left + q_right)
    x   = w_self x~ + m + (x_half - x)    (x_half: the node after Adam)

with x~ = x0 and m = (1 - w_self) x0 at the start, w_side = (1 - w_self) / 2.
The uniform bits u are the traffic's noise rule (``assumed.noise`` of
its file): node i's at step k are ``jax.random.uniform(fold_in(fold_in(
PRNGKey(noise_seed), k), i), (R, 512))``, read row-major, each leaf in name
order taking whole rows of 512 (R: those rows rounded up to 32).  Node i
sits on device i (modulo the devices there are), so that the nodes' state
fits one chip each; its neighbours' q are copied to it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts

__all__ = ["node_rows", "node_key", "noise_tree", "run_ring"]


def node_rows(rows: list[tuple], nodes: int) -> list[list[tuple]]:
    """Each node's (tokens, labels) of each step: the step's rows split in
    ``nodes`` equal consecutive parts, node i the i-th."""
    out = [[] for _ in range(nodes)]
    for tokens, labels in rows:
        for i, (t, l) in enumerate(zip(np.split(tokens, nodes),
                                       np.split(labels, nodes))):
            out[i].append((t, l))
    return out


def node_key(key, k: int, i: int):
    """The key of node i's uniform bits at step k."""
    return jax.random.fold_in(jax.random.fold_in(key, k), i)


@functools.partial(jax.jit, static_argnums=(1,))
def noise_tree(key, shapes: tuple[tuple[str, tuple[int, ...]], ...]) -> dict:
    """The uniform bits of one node and step, as a tree of the leaves'
    shapes (``shapes``: (name, shape) in name order)."""
    sizes = [math.prod(s) for _, s in shapes]
    rows = counts.wire_rows(sizes)
    u = jax.random.uniform(key, (rows, counts.BLOCK), jnp.float32)
    out, row = {}, 0
    for (name, shape), n in zip(shapes, sizes):
        r = math.ceil(max(n, 1) / counts.BLOCK)
        out[name] = u[row:row + r].reshape(-1)[:n].reshape(shape)
        row += r
    return out


@jax.jit
def _codes(x, xt, u, step):
    """q = D c of every leaf (see the module's doc)."""
    def leaf(x, xt, u):
        s = (x - xt) / step
        lo = jnp.floor(s)
        return jnp.clip(lo + (u < s - lo).astype(jnp.float32),
                        -127.0, 127.0) * step
    return jax.tree.map(leaf, x, xt, u)


@jax.jit
def _mix(x, x_half, xt, m, q, q_l, q_r, w_self, w_side):
    """The node's shadows after the exchange, and its next weights."""
    xt = jax.tree.map(jnp.add, xt, q)
    m = jax.tree.map(lambda m, a, b: m + w_side * (a + b), m, q_l, q_r)
    x = jax.tree.map(lambda t, mm, h, p: (w_self * t + mm) + (h - p),
                     xt, m, x_half, x)
    return x, xt, m


def run_ring(R, conf: dict, seed: int, batches: list[list[tuple]],
             traffic: dict, precision: str = "f32",
             exchange: bool = True) -> dict:
    """Train the ring for ``len(batches[0])`` steps, node i on
    ``batches[i][step]``.  Returns the nodes' mean loss of every step, and
    per ``node<i>.<leaf>`` the first step's gradient and the change of the
    parameters over all the steps, as host arrays.  Without ``exchange``
    each node trains alone."""
    m = R.Model.from_config(conf)
    opt, ex = conf["optimizer"], traffic["exchange"]
    lr, b1, b2, eps = (np.float32(opt[k]) for k in ("lr", "b1", "b2", "eps"))
    w_self = np.float32(ex["self_weight"])
    w_side = np.float32((1.0 - ex["self_weight"]) / 2.0)
    n = len(batches)
    devs = jax.devices()
    dev = [devs[i % len(devs)] for i in range(n)]
    grad = R.grad_fn(m, precision)
    x0 = R.init_params(m, seed)
    shapes = tuple((k, tuple(v.shape)) for k, v in sorted(x0.items()))
    x0 = [jax.device_put(x0, d) for d in dev]
    x = list(x0)
    mu = [jax.tree.map(jnp.zeros_like, p) for p in x]
    nu = [jax.tree.map(jnp.zeros_like, p) for p in x]
    t = [jax.device_put(jnp.zeros((), jnp.int32), d) for d in dev]
    xt = list(x0)
    agg = [jax.tree.map(lambda a: (1 - w_self) * a, p) for p in x0]
    key = jax.random.PRNGKey(ex.get("noise_seed", 0))
    losses, grad1 = [], None
    for k in range(1, len(batches[0]) + 1):
        half, step_loss, g1 = [], [], []
        for i in range(n):
            tokens, labels = (jax.device_put(a, dev[i])
                              for a in batches[i][k - 1])
            loss, g = grad(x[i], tokens, labels)
            step_loss.append(loss)
            if grad1 is None:
                g1.append(g)
            h, mu[i], nu[i], t[i] = R.adam(x[i], g, mu[i], nu[i], t[i], lr,
                                           b1, b2, eps)
            half.append(h)
        if grad1 is None:
            grad1 = {f"node{i}.{name}": np.asarray(a)
                     for i, g in enumerate(g1) for name, a in g.items()}
        losses.append(float(np.mean([float(v) for v in step_loss])))
        if not exchange:
            x = half
            continue
        step = np.float32(ex["fixed_step0"]) / np.float32(k) ** np.float32(
            ex["gamma"])
        q = []
        for i in range(n):
            u = noise_tree(jax.device_put(node_key(key, k, i), dev[i]),
                           shapes)
            q.append(_codes(x[i], xt[i], u, step))
        nxt = []
        for i in range(n):
            q_l = jax.device_put(q[(i - 1) % n], dev[i])
            q_r = jax.device_put(q[(i + 1) % n], dev[i])
            xi, xt[i], agg[i] = _mix(x[i], half[i], xt[i], agg[i], q[i], q_l,
                                     q_r, w_self, w_side)
            nxt.append(xi)
        x = nxt
    change = {f"node{i}.{name}": np.asarray(a - x0[i][name])
              for i in range(n) for name, a in x[i].items()}
    return {"loss": losses, "grad1": grad1, "change": change}
