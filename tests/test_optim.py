"""Optimizers, schedules, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.config import OptimizerConfig
from repro.optim import (clip_by_global_norm, global_norm, make_optimizer,
                         make_schedule)
from repro.optim.compression import (compress_tree, decompress_tree,
                                     init_residual)


def test_adamw_minimizes_quadratic():
    opt_cfg = OptimizerConfig(lr=0.05, weight_decay=0.0, warmup_steps=1,
                              total_steps=300, schedule="constant")
    opt = make_optimizer(opt_cfg)
    target = jnp.array([[1.0, -2.0], [3.0, 0.5]])
    params = {"w": jnp.zeros((2, 2))}
    state = opt.init(params)
    step = jnp.zeros((), jnp.int32)
    for i in range(300):
        grads = {"w": 2 * (params["w"] - target)}
        params, state = opt.update(grads, state, params, step + i)
    assert float(jnp.max(jnp.abs(params["w"] - target))) < 0.05


def test_adamw_bf16_state_dtype():
    opt = make_optimizer(OptimizerConfig(state_dtype="bfloat16"))
    params = {"w": jnp.ones((4, 4))}
    st_ = opt.init(params)
    assert st_["m"]["w"].dtype == jnp.bfloat16


def test_grad_clip():
    tree = {"a": jnp.full((10,), 10.0)}
    clipped, gn = clip_by_global_norm(tree, 1.0)
    assert float(gn) == pytest.approx(np.sqrt(1000.0))
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_schedule_warmup_and_decay():
    cfg = OptimizerConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    s = make_schedule(cfg)
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(1e-3)
    assert float(s(100)) < float(s(50)) < float(s(10))


def test_compression_roundtrip_small_error():
    rng = jax.random.PRNGKey(0)
    g = {"w": jax.random.normal(rng, (256,)) * 0.01}
    r = init_residual(g)
    q, s, r2 = compress_tree(g, r)
    rec = decompress_tree(q, s)
    err = float(jnp.max(jnp.abs(rec["w"] - g["w"])))
    assert err <= float(s["w"]) / 2 + 1e-9
    # residual holds exactly the quantization error
    np.testing.assert_allclose(np.asarray(r2["w"]),
                               np.asarray(g["w"] - rec["w"]), atol=1e-7)


def _check_compression_error_feedback_unbiased(seed, steps):
    """Property: with a CONSTANT gradient, error feedback makes the mean of
    decompressed gradients converge to the true gradient."""
    rng = np.random.default_rng(seed)
    g_true = {"w": jnp.asarray(rng.standard_normal(64).astype(np.float32))}
    r = init_residual(g_true)
    acc = jnp.zeros(64)
    for _ in range(steps):
        q, s, r = compress_tree(g_true, r)
        acc = acc + decompress_tree(q, s)["w"]
    mean = acc / steps
    # bias shrinks as 1/steps: |mean - g| <= max_residual/steps
    bound = float(jnp.max(jnp.abs(g_true["w"]))) / 127.0 * (1.0 + 2.0 / steps)
    assert float(jnp.max(jnp.abs(mean - g_true["w"]))) <= bound + 1e-6


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 500), steps=st.integers(3, 20))
def test_compression_error_feedback_unbiased(seed, steps):
    _check_compression_error_feedback_unbiased(seed, steps)
