"""Chunked-parallel recurrences vs sequential oracles (mLSTM, sLSTM, SSD)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.models.mamba2 import ssd_chunked, ssd_step
from repro.models.xlstm import (mlstm_chunked, mlstm_recurrent_step,
                                slstm_scan)


def _mlstm_ref(q, k, v, log_f, log_i):
    b, s, h, d = q.shape
    state = (jnp.zeros((b, h, d, d)), jnp.zeros((b, h, d)),
             jnp.full((b, h), -1e30))
    hs = []
    for t in range(s):
        state, ht = mlstm_recurrent_step(state, q[:, t], k[:, t], v[:, t],
                                         log_f[:, t], log_i[:, t])
        hs.append(ht)
    return jnp.stack(hs, 1), state


@pytest.mark.parametrize("s,chunk", [(16, 4), (37, 8), (33, 33), (20, 64)])
def test_mlstm_chunked_matches_recurrent(s, chunk):
    rng = np.random.default_rng(s * 131 + chunk)
    b, h, d = 2, 3, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    log_f = jnp.asarray(-np.abs(rng.normal(0, 1, (b, s, h))), jnp.float32)
    log_i = jnp.asarray(rng.normal(0, 1, (b, s, h)), jnp.float32)
    ref, ref_state = _mlstm_ref(q, k, v, log_f, log_i)
    out, state = mlstm_chunked(q, k, v, log_f, log_i, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(ref_state[0]),
                               rtol=1e-4, atol=1e-4)


def test_mlstm_state_carry_across_calls():
    """Two chunked calls with carried state == one call over the full seq."""
    rng = np.random.default_rng(0)
    b, s, h, d = 1, 24, 2, 4
    mk = lambda sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    q, k, v = mk((b, s, h, d)), mk((b, s, h, d)), mk((b, s, h, d))
    log_f = -jnp.abs(mk((b, s, h)))
    log_i = mk((b, s, h))
    full, _ = mlstm_chunked(q, k, v, log_f, log_i, chunk=6)
    h1, st = mlstm_chunked(q[:, :12], k[:, :12], v[:, :12],
                           log_f[:, :12], log_i[:, :12], chunk=6)
    h2, _ = mlstm_chunked(q[:, 12:], k[:, 12:], v[:, 12:],
                          log_f[:, 12:], log_i[:, 12:], chunk=6, state=st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(full), rtol=1e-4, atol=1e-4)


def _ssd_ref(x, dt, A, B, C):
    b, s, h, p = x.shape
    n = B.shape[-1]
    st = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        st, y = ssd_step(st, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        ys.append(y)
    return jnp.stack(ys, 1), st


@pytest.mark.parametrize("s,chunk", [(16, 4), (29, 8), (29, 29), (12, 64)])
def test_ssd_chunked_matches_recurrent(s, chunk):
    rng = np.random.default_rng(s * 7 + chunk)
    b, h, p, n = 2, 3, 8, 6
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(0.5, 0.3, (b, s, h))), jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(1, 0.3, h)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    ref, ref_st = _ssd_ref(x, dt, A, B, C)
    out, st = ssd_chunked(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(ref_st),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), s=st.integers(2, 24),
       chunk=st.integers(1, 32))
def test_property_ssd_chunk_invariance(seed, s, chunk):
    """Result must be independent of the chunk size (exactness property)."""
    rng = np.random.default_rng(seed)
    b, h, p, n = 1, 2, 4, 3
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(0.5, 0.3, (b, s, h))), jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(1, 0.3, h)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    out1, _ = ssd_chunked(x, dt, A, B, C, chunk=chunk)
    out2, _ = ssd_chunked(x, dt, A, B, C, chunk=s)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=2e-4, atol=2e-4)


def test_slstm_state_carry():
    rng = np.random.default_rng(0)
    b, s, h, d = 2, 14, 2, 4
    gates = jnp.asarray(rng.normal(size=(b, s, h, 4, d)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(h, 4, d, d)) * 0.2, jnp.float32)
    full, _ = slstm_scan(gates, r)
    h1, st = slstm_scan(gates[:, :7], r)
    h2, _ = slstm_scan(gates[:, 7:], r, state=st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(full), rtol=1e-5, atol=1e-5)


def test_moe_dispatch_vs_reference():
    from repro.models import moe
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=48, vocab=100, head_dim=16,
                      n_experts=8, top_k=2, capacity_factor=8.0,
                      dtype="float32", remat=False)
    params, _ = moe.init(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32)
    out, aux = moe.moe_mlp(x, lp, cfg)
    ref = moe.moe_mlp_reference(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) > 0


def test_moe_capacity_drops_bounded():
    """With capacity 1.0 the dropped fraction must stay small for balanced
    routing, and outputs stay finite."""
    from repro.models import moe
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=48, vocab=100, head_dim=16,
                      n_experts=4, top_k=2, capacity_factor=1.0,
                      dtype="float32", remat=False)
    params, _ = moe.init(jax.random.PRNGKey(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 32, 32)), jnp.float32)
    out, _ = moe.moe_mlp(x, lp, cfg)
    assert bool(jnp.isfinite(out).all())
