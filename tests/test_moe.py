"""Gather-only MoE vs dense every-expert reference (fwd + grads)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import moe as M
from repro.models.param import materialize


pytestmark = pytest.mark.slow  # model-heavy; run with -m slow


def _dense_ref(cfg, p, x):
    E, k = cfg.n_experts, cfg.moe_topk
    logits = jnp.einsum("gnd,de->gne", x, p["router"])
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / w.sum(-1, keepdims=True)
    gate = jnp.einsum("gnd,edf->gnef", x, p["w_gate"])
    up = jnp.einsum("gnd,edf->gnef", x, p["w_up"])
    out = jnp.einsum("gnef,efd->gned", jax.nn.silu(gate) * up, p["w_down"])
    cmb = jnp.zeros((*x.shape[:2], E))
    for j in range(k):
        cmb = cmb + w[..., j:j + 1] * jax.nn.one_hot(idx[..., j], E)
    return jnp.einsum("gne,gned->gnd", cmb, out)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(smoke_config("grok-1-314b"), dtype="float32",
                              capacity_factor=8.0)  # dropless at this scale
    p = materialize(M.moe_specs(cfg), jax.random.key(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (3, 16, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


def test_moe_forward_matches_dense(setup):
    cfg, p, x = setup
    y, aux = M.apply_moe(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_dense_ref(cfg, p, x)),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) > 0


def test_moe_grads_match_dense(setup):
    cfg, p, x = setup

    def f1(p, x):
        return jnp.sum(jnp.sin(M.apply_moe(cfg, p, x)[0]))

    def f2(p, x):
        return jnp.sum(jnp.sin(_dense_ref(cfg, p, x)))

    g1 = jax.grad(f1, argnums=(0, 1))(p, x)
    g2 = jax.grad(f2, argnums=(0, 1))(p, x)
    for key in ("w_up", "w_gate", "w_down"):
        np.testing.assert_allclose(np.asarray(g1[0][key]),
                                   np.asarray(g2[0][key]),
                                   rtol=1e-4, atol=1e-5, err_msg=key)
    np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]),
                               rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_tokens():
    """At capacity_factor≈0 almost everything is dropped → output ≈ 0."""
    cfg = dataclasses.replace(smoke_config("grok-1-314b"), dtype="float32",
                              capacity_factor=1e-6)
    p = materialize(M.moe_specs(cfg), jax.random.key(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    y, _ = M.apply_moe(cfg, p, x)
    y_full, _ = M.apply_moe(
        dataclasses.replace(cfg, capacity_factor=8.0), p, x)
    assert float(jnp.abs(y).sum()) < float(jnp.abs(y_full).sum())


def test_moe_shardmap_matches_gather(subproc):
    """EP-psum shard_map MoE ≡ gather MoE (fwd + all grads) on a 2×4 mesh."""
    out = subproc("""
import jax, jax.numpy as jnp, dataclasses
from repro.configs import smoke_config
from repro.models import moe as M
from repro.models.param import materialize
cfg = dataclasses.replace(smoke_config("moonshot-v1-16b-a3b"),
                          dtype="float32", capacity_factor=8.0)
p = materialize(M.moe_specs(cfg), jax.random.key(0), dtype=jnp.float32)
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32)
from repro.core._jax_compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    def f(fn):
        def loss(p, x):
            y, aux = fn(cfg, p, x)
            return jnp.sum(jnp.sin(y)) + aux
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(p, x)
    y0, a0 = jax.jit(lambda p, x: M.apply_moe(cfg, p, x))(p, x)
    y1, a1 = jax.jit(lambda p, x: M.apply_moe_shardmap(cfg, p, x))(p, x)
    assert float(jnp.max(jnp.abs(y0 - y1))) < 1e-5
    assert abs(float(a0 - a1)) < 1e-6
    g0, g1 = f(M.apply_moe), f(M.apply_moe_shardmap)
    for k in ("w_up", "w_gate", "w_down", "router"):
        assert float(jnp.max(jnp.abs(g0[0][k] - g1[0][k]))) < 1e-4, k
    assert float(jnp.max(jnp.abs(g0[1] - g1[1]))) < 1e-4
print("SHARDMAP_MOE_OK")
""", devices=8, timeout=420)
    assert "SHARDMAP_MOE_OK" in out


def test_local_attn_chunked_exact():
    """Block-local windowed attention ≡ masked full attention."""
    import dataclasses as dc
    from repro.models.model import LModel
    from repro.models.param import materialize as mat
    cfg0 = dc.replace(smoke_config("gemma3-4b"), dtype="float32")
    cfg1 = dc.replace(cfg0, local_attn_chunked=True)
    m0, m1 = LModel(cfg0), LModel(cfg1)
    p = mat(m0.param_specs(), jax.random.key(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg0.vocab_size)
    np.testing.assert_allclose(np.asarray(m0.logits_seq(p, toks)),
                               np.asarray(m1.logits_seq(p, toks)),
                               rtol=1e-4, atol=1e-4)


def test_moe_decode_single_token():
    cfg = dataclasses.replace(smoke_config("moonshot-v1-16b-a3b"),
                              dtype="float32")
    p = materialize(M.moe_specs(cfg), jax.random.key(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(2), (4, 1, cfg.d_model))
    y, _ = M.apply_moe(cfg, p, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
