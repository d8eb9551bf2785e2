"""Optimizer / data pipeline / checkpoint substrate tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.data import SyntheticLMDataset
from repro.optim import Adam, Momentum, Sgd, by_name
from repro.optim.schedules import (constant_schedule, cosine_warmup_schedule,
                                   inverse_power_schedule)


def _quad_params():
    return {"a": jnp.asarray([1.0, -2.0, 3.0]),
            "nested": ({"b": jnp.ones((2, 2))},)}


@pytest.mark.parametrize("opt", [Sgd(), Momentum(), Momentum(nesterov=True),
                                 Adam()])
def test_optimizer_reduces_quadratic(opt):
    params = _quad_params()
    target = jax.tree.map(lambda p: jnp.full_like(p, 0.5), params)

    def loss(p):
        d = jax.tree.map(lambda a, b: jnp.sum((a - b) ** 2), p, target)
        return jax.tree.reduce(lambda a, b: a + b, d)

    state = opt.init(params)
    lr = 0.05
    l0 = float(loss(params))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = opt.step(state, params, g, lr)
    assert float(loss(params)) < 1e-3 * l0


def test_sgd_exact_update():
    """The paper's gradient step: x <- x - alpha*g, bit-exact."""
    opt = Sgd()
    p = {"w": jnp.asarray([1.0, 2.0])}
    g = {"w": jnp.asarray([0.5, -1.0])}
    new, _ = opt.step(opt.init(p), p, g, 0.1)
    np.testing.assert_allclose(np.asarray(new["w"]), [0.95, 2.1], rtol=1e-6)


def test_optimizer_state_mirrors_param_tree():
    opt = Adam()
    params = _quad_params()
    st = opt.init(params)
    assert jax.tree_util.tree_structure(st["m"]) == \
        jax.tree_util.tree_structure(params)


def test_schedules():
    assert float(constant_schedule(0.1)(jnp.asarray(100))) == pytest.approx(0.1)
    inv = inverse_power_schedule(1.0, 0.5)
    assert float(inv(jnp.asarray(100))) == pytest.approx(0.1)
    cos = cosine_warmup_schedule(1.0, warmup=10, total=100)
    assert float(cos(jnp.asarray(5))) == pytest.approx(0.5)
    assert float(cos(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-6)


def test_registry():
    assert isinstance(by_name("adam"), Adam)
    with pytest.raises(KeyError):
        by_name("nope")


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_sharded():
    ds = SyntheticLMDataset(vocab_size=256, seq_len=32, global_batch=8,
                            n_shards=4, seed=7)
    b1 = ds.batch(step=3, shard=1)
    b2 = ds.batch(step=3, shard=1)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = ds.batch(step=3, shard=2)
    assert not np.array_equal(b1["tokens"], b3["tokens"])  # distinct f_i
    b4 = ds.batch(step=4, shard=1)
    assert not np.array_equal(b1["tokens"], b4["tokens"])
    g = ds.global_batch_arrays(step=3)
    assert g["tokens"].shape == (8, 32)
    np.testing.assert_array_equal(g["tokens"][2:4], b1["tokens"])
    assert g["labels"].shape == (8, 32)
    # next-token alignment
    np.testing.assert_array_equal(g["tokens"][:, 1:], g["labels"][:, :-1])


def test_data_is_learnable():
    """The Markov structure must make loss << log(V) reachable: check that
    the empirical successor distribution is concentrated."""
    ds = SyntheticLMDataset(vocab_size=128, seq_len=256, global_batch=16, seed=1)
    g = ds.global_batch_arrays(0)
    toks = g["tokens"]
    # for each token, successors should mostly come from its 8-entry table
    hits = 0
    total = 0
    for row in toks[:4]:
        for a, b in zip(row[:-1], row[1:]):
            total += 1
            if b in ds._succ[a]:
                hits += 1
    assert hits / total > 0.7


def test_whisper_frames():
    ds = SyntheticLMDataset(vocab_size=64, seq_len=16, global_batch=2,
                            enc_frames=8, d_model=32)
    b = ds.batch(0)
    assert b["enc_frames"].shape == (2, 8, 32)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
            "consensus": {"x_tilde": np.ones((4,), np.float32)},
            "step": np.asarray(17, np.int32)}
    d = str(tmp_path)
    save_checkpoint(d, 17, tree)
    save_checkpoint(d, 42, tree)
    assert latest_step(d) == 42
    loaded, step = load_checkpoint(d, tree)
    assert step == 42
    np.testing.assert_array_equal(loaded["params"]["w"], tree["params"]["w"])


def test_checkpoint_rejects_mismatched_template(tmp_path):
    tree = {"w": np.ones((2, 2), np.float32)}
    save_checkpoint(str(tmp_path), 1, tree)
    with pytest.raises(ValueError):
        load_checkpoint(str(tmp_path), {"w": np.ones((3, 3), np.float32)})
    with pytest.raises(ValueError):
        load_checkpoint(str(tmp_path), {"w": np.ones((2, 2)), "extra": np.ones(1)})


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    """The persistent compile cache lives in JAX_COMPILATION_CACHE_DIR when
    it is set, else in the fixed, git-ignored ``.jax_cache`` of the
    checkout."""
    from repro.launch.compile_cache import (DEFAULT_CACHE_DIR,
                                            enable_compile_cache)
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_set:
        assert path == str(tmp_path)
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache") \
            == str(DEFAULT_CACHE_DIR)
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
