"""Multi-device tests (subprocesses with fake devices — the main pytest
process must keep seeing the single real CPU device):

- MoE expert-parallel shard_map path == dense reference path;
- shard_map simulation backend == vmap backend (paper core at scale);
- elastic restore: checkpoint saved on one dp degree restores onto another;
- loop-aware HLO cost analyzer counts collectives on a sharded module.
"""
import pytest


def test_moe_ep_matches_dense(subproc):
    subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_smoke_config
from repro.models.moe import apply_moe, moe_specs
from repro.common import init_params
import dataclasses

cfg = get_smoke_config("kimi-k2-1t-a32b")
cfg = dataclasses.replace(cfg, d_model=64)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
specs = moe_specs(cfg, tp=2)
params = init_params(jax.random.PRNGKey(0), specs)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64), jnp.float32)
y_dense, aux_d = apply_moe(cfg, params, x, __import__("repro.common", fromlist=["DTypePolicy"]).DTypePolicy(), mesh=None)
with jax.set_mesh(mesh):
    y_ep, aux_e = jax.jit(lambda p, x: apply_moe(cfg, p, x,
        __import__("repro.common", fromlist=["DTypePolicy"]).DTypePolicy(), mesh=mesh))(params, x)
# EP uses capacity-dropless path at this size: must match dense exactly-ish
np.testing.assert_allclose(np.asarray(y_ep, np.float32), np.asarray(y_dense, np.float32),
                           rtol=2e-2, atol=2e-2)
print("EP==dense OK", float(jnp.abs(y_ep - y_dense).max()))
""",
        n_devices=8,
    )


def test_shard_map_backend_matches_vmap(subproc):
    subproc(
        """
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.core import segmentation as sg
from repro.core.controller import Controller
from repro.vp import workloads as wl

layer = wl.Layer("t", "t", 8, 8, 4)
descs = sg.uniform(2, 2)
job = wl.cim_workload(layer, mgr_segments=[0, 1], cim_ids_per_mgr={0: (0, 1), 1: (2, 3)})
cfg, states, pending = sg.build(descs, programs=job["programs"], dram_words=job["dram"],
                                crossbars=job["crossbars"], scratch_init=job["scratch"],
                                channel_latency=2000)
mesh = make_mesh((2,), ("segment",))
res = {}
for backend, kw in (("vmap", {}), ("shard_map", {"mesh": mesh})):
    ctl = Controller(cfg, states, pending, backend=backend, quantum=1000, **kw)
    ctl.run(max_rounds=200, check_every=1)
    st = ctl.result_states()
    res[backend] = (np.asarray(st["dram"]["data"][0][:4096]), np.asarray(st["time"]),
                    np.asarray(st["stats"]["instrs"]))
for a, b in zip(res["vmap"], res["shard_map"]):
    np.testing.assert_array_equal(a, b)
print("shard_map == vmap OK")
""",
        n_devices=2,
    )


def test_shard_map_backend_matches_vmap_snn(subproc):
    """SNN spike traffic over the shard_map backend == vmap, bit-exact."""
    subproc(
        """
import jax, numpy as np
from repro import snn
from repro.launch.mesh import make_mesh
from repro.core.controller import Controller

job = snn.snn_inference_job((16, 12, 8), t_steps=6, rate=0.6, seed=5)
descs = snn.segmentation_for(2, "uniform", n_segments=2)
cfg, states, pending, meta = snn.build_snn(job.layers, descs, job.raster)
mesh = make_mesh((2,), ("segment",))
res = {}
for backend, kw in (("vmap", {}), ("shard_map", {"mesh": mesh})):
    ctl = Controller(cfg, states, pending, backend=backend, quantum=32, **kw)
    ctl.run(max_rounds=100, check_every=1)
    st = ctl.result_states()
    res[backend] = (np.asarray(st["cims"]["spike_counts"]),
                    np.asarray(st["cims"]["v"]), np.asarray(st["cims"]["ticks"]))
for a, b in zip(res["vmap"], res["shard_map"]):
    np.testing.assert_array_equal(a, b)
np.testing.assert_array_equal(
    np.asarray(res["vmap"][0][meta["out_unit"][0], meta["out_unit"][1], :meta["n_out"]]),
    job.expected_counts)
print("shard_map SNN == vmap OK")
""",
        n_devices=2,
    )


def test_elastic_checkpoint_restore(subproc, tmp_path):
    """Save under dp=4 sharding, restore under dp=2 — logical arrays identical."""
    subproc(
        f"""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.train import checkpoint as ckpt

mesh4 = make_mesh((4, 2), ("data", "model"))
x = jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)
xs = jax.device_put(x, NamedSharding(mesh4, P("data", "model")))
ckpt.save(r"{tmp_path}", 5, {{"w": xs}})
mesh2 = make_mesh((2, 4), ("data", "model"))
restored, at = ckpt.restore(r"{tmp_path}", {{"w": x}},
    shardings={{"w": NamedSharding(mesh2, P("data", "model"))}})
assert at == 5
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
assert restored["w"].sharding.mesh.shape["data"] == 2
print("elastic restore OK")
""",
        n_devices=8,
    )


def test_hlo_cost_counts_sharded_collectives(subproc):
    subproc(
        """
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.analysis.hlo_cost import analyze

mesh = make_mesh((4, 2), ("data", "model"))
def f(w, x):
    def body(c, _):
        return jnp.tanh(c @ w), None
    y, _ = jax.lax.scan(body, x, None, length=7)
    return y.sum()
w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
with jax.set_mesh(mesh):
    c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "model")),
                                 NamedSharding(mesh, P("data", None))),
                out_shardings=NamedSharding(mesh, P())).lower(w, x).compile()
r = analyze(c.as_text())
expect = 7 * 2 * 256**3 / 8  # per-device
assert abs(r.flops - expect) / expect < 0.05, (r.flops, expect)
assert r.coll > 0, "collectives must be counted"
print("hlo_cost sharded OK", r.flops, r.coll)
""",
        n_devices=8,
    )
