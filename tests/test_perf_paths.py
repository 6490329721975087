"""Equivalence tests for the §Perf beyond-paper execution paths:
flash train attention (custom VJP) and the decode MoE token-replication
path must match their reference implementations."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L


def test_flash_train_matches_dense_fwd_bwd():
    key = jax.random.PRNGKey(0)
    b, s, h, d = 2, 512, 2, 32
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), jnp.float32)

    o_d = L.dense_attention(q, k, v, causal=True)
    o_f = L.flash_attention_train(q, k, v, 128, 128)
    np.testing.assert_allclose(np.asarray(o_d), np.asarray(o_f), atol=2e-5)

    def make_loss(fn):
        return lambda q, k, v: (fn(q, k, v) * (q + 1)).sum()

    gd = jax.grad(make_loss(lambda q, k, v: L.dense_attention(q, k, v, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(make_loss(lambda q, k, v: L.flash_attention_train(q, k, v, 128, 128)),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b2 in zip("qkv", gd, gf):
        rel = float(jnp.abs(a - b2).max() / (jnp.abs(a).max() + 1e-9))
        assert rel < 1e-4, (name, rel)


def test_flash_in_model_matches_dense_in_model():
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models.model import build

    cfg = get_smoke_config("qwen3-1.7b")
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(0), (2, 512), 0, cfg.vocab_size)}
    losses = {}
    for impl in ("dense", "flash"):
        c = dataclasses.replace(cfg, attn_impl=impl)
        m = build(c, tp=1)
        params = m.init(jax.random.PRNGKey(0))
        losses[impl], _ = m.loss(params, batch)
    np.testing.assert_allclose(float(losses["dense"]), float(losses["flash"]), rtol=2e-3)


def test_moe_decode_path_matches_dense(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.launch.mesh import make_mesh
from repro.configs import get_smoke_config
from repro.models.moe import apply_moe
from repro.models.transformer import decoder_specs
from repro.models.moe import moe_specs
from repro.common import init_params, DTypePolicy

cfg = get_smoke_config("kimi-k2-1t-a32b")
cfg = dataclasses.replace(cfg, d_model=64)
mesh = make_mesh((2, 2), ("data", "model"))
specs = moe_specs(cfg, tp=2)
params = init_params(jax.random.PRNGKey(0), specs)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 64), jnp.float32)  # decode shape
pol = DTypePolicy()
y_ref, _ = apply_moe(cfg, params, x, pol, mesh=None)
with jax.set_mesh(mesh):
    y_dec, _ = jax.jit(lambda p, x: apply_moe(cfg, p, x, pol, mesh=mesh, decode=True))(params, x)
np.testing.assert_allclose(np.asarray(y_dec, np.float32), np.asarray(y_ref, np.float32),
                           rtol=2e-2, atol=2e-2)
print("moe decode path OK", float(jnp.abs(y_dec - y_ref).max()))
""",
        n_devices=4,
    )


def test_ssm_chunked_restructure_matches_kernel_ref():
    """mamba1 per-chunk expansion (hillclimb) still equals the plain scan."""
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.models.ssm import mamba1_block, mamba1_specs
    from repro.common import init_params, DTypePolicy

    cfg = get_smoke_config("falcon-mamba-7b")
    p = init_params(jax.random.PRNGKey(0), mamba1_specs(cfg, tp=1))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model), jnp.float32) * 0.1
    pol = DTypePolicy()
    y, st = mamba1_block(cfg, p, x, pol)
    # step-by-step decode over the same inputs must match the chunked result
    import jax as _jax

    st2 = None
    outs = []
    for t in range(8):
        xt = x[:, t : t + 1]
        if st2 is None:
            din = cfg.ssm.expand * cfg.d_model
            st2 = {
                "conv": jnp.zeros((2, cfg.ssm.d_conv - 1, din), jnp.bfloat16),
                "ssm": jnp.zeros((2, din, cfg.ssm.d_state), jnp.float32),
            }
        yt, st2 = mamba1_block(cfg, p, xt, pol, state=st2)
        outs.append(yt)
    step_y = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(step_y, np.float32), np.asarray(y[:, :8], np.float32), atol=3e-2
    )
