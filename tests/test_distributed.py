"""Distribution layer: sharding rules, multi-device train step, compressed
gradients, elastic checkpoint restore onto a different mesh (subprocesses
with fake host devices)."""
import numpy as np
import pytest

from conftest import run_subprocess


def test_sharding_rules_unit():
    import jax

    from repro.dist.sharding import default_rules, spec_for_axes, spec_for_axes_shaped
    from jax.sharding import PartitionSpec as P

    rules = default_rules(True, ("data", "model"))
    assert spec_for_axes(("embed", "mlp"), rules) == P(None, ("model", "data"))
    # duplicate mesh axes are never reused
    s = spec_for_axes(("mlp", "vocab"), rules)
    flat = []
    for e in s:
        if isinstance(e, tuple):
            flat += list(e)
        elif e:
            flat.append(e)
    assert len(flat) == len(set(flat))


def test_train_shardings_plumbing():
    """``train_shardings`` derives the whole launch plumbing: fitted param
    shardings, optimizer state by structure (moments follow params, step
    replicates), and the batch sharding — no hand-rolled osh dicts."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import LMConfig, TransformerLM
    from repro.train.optimizer import AdamW
    from repro.dist.sharding import train_shardings

    cfg = LMConfig(
        name="t", n_layers=1, d_model=32, n_q=2, n_kv=1, head_dim=16, d_ff=64,
        vocab=128, act_dtype=jnp.float32,
    )
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.key(0))
    opt_state = AdamW(lr=1e-3).init(params)
    mesh = jax.make_mesh((1,), ("data",))
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    sh = train_shardings(mesh, lm.axes(), abstract, opt_state, batch_size=4)
    # params: every leaf got a NamedSharding
    assert all(isinstance(s, NamedSharding) for s in jax.tree.leaves(sh.params))
    # moments mirror the param shardings exactly; the step counter replicates
    assert jax.tree.structure(sh.opt_state["m"]) == jax.tree.structure(sh.params)
    assert sh.opt_state["m"] == sh.params
    assert sh.opt_state["v"] == sh.params
    assert sh.opt_state["step"].spec == P()
    # batch leading dim maps to the data-like axes (1-device: fitted away or data)
    assert isinstance(sh.batch, NamedSharding)
    # master-weight states follow params too (structure-matched branch)
    opt_state_mw = AdamW(lr=1e-3, master_weights=True).init(params)
    sh2 = train_shardings(mesh, lm.axes(), abstract, opt_state_mw, batch_size=4)
    assert sh2.opt_state["master"] == sh2.params
    # the whole tree is consumable by device_put (smoke on the 1-device mesh)
    jax.block_until_ready(jax.device_put(params, sh.params))
    jax.block_until_ready(jax.device_put(opt_state, sh.opt_state))


@pytest.mark.slow
def test_mesh_sharded_train_step_matches_single_device():
    code = '''
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models import LMConfig, TransformerLM
from repro.train.optimizer import AdamW
from repro.train.steps import make_lm_train_step
from repro.dist.sharding import default_rules, tree_shardings_shaped, batch_sharding
from repro.data import token_batches

cfg = LMConfig(name="t", n_layers=2, d_model=64, n_q=4, n_kv=2, head_dim=16, d_ff=128,
               vocab=256, act_dtype=jnp.float32)
lm = TransformerLM(cfg)
params = lm.init(jax.random.key(0))
opt = AdamW(lr=1e-3)
opt_state = opt.init(params)
batch = {k: jnp.asarray(v) for k, v in next(token_batches(8, 32, 256, seed=0)).items()}
step = make_lm_train_step(lm, opt)

# single device reference
p1, s1, m1 = jax.jit(step)(params, opt_state, batch)

# explicit NamedShardings only: works on every jax with jax.make_mesh
mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = default_rules(True, mesh.axis_names)
psh = tree_shardings_shaped(mesh, lm.axes(), jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params), rules)
osh = {"m": psh, "v": psh, "step": NamedSharding(mesh, P())}
bsh = batch_sharding(mesh, 8, rules)
p8, s8, m8 = jax.jit(step, in_shardings=(psh, osh, {"tokens": bsh, "labels": bsh}))(params, opt_state, batch)
assert abs(float(m1["loss"]) - float(m8["loss"])) < 1e-3, (float(m1["loss"]), float(m8["loss"]))
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
    np.testing.assert_allclose(np.float32(a), np.float32(b), atol=2e-3)
print("SHARDED==SINGLE OK")
'''
    out = run_subprocess(code, devices=8)
    assert "SHARDED==SINGLE OK" in out


@pytest.mark.slow
def test_compressed_pod_gradients():
    code = '''
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist import make_compressed_dp_grad_fn, zeros_like_error
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
def loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"])**2)
params = {"w": jnp.ones((8, 4))}
batch = {"x": jax.random.normal(jax.random.key(0), (16, 8)),
         "y": jax.random.normal(jax.random.key(1), (16, 4))}
gf = jax.jit(make_compressed_dp_grad_fn(loss_fn, mesh, P(("pod", "data"))))
g, err = gf(params, batch, zeros_like_error(params, 2))
g_ref = jax.grad(loss_fn)(params, batch)
rel = float(jnp.abs(g["w"] - g_ref["w"]).max() / jnp.abs(g_ref["w"]).max())
assert rel < 0.02, rel
# error feedback: a second identical step must not diverge
g2, err2 = gf(params, batch, err)
rel2 = float(jnp.abs(g2["w"] - g_ref["w"]).max() / jnp.abs(g_ref["w"]).max())
assert rel2 < 0.04, rel2
print("COMPRESSED OK")
'''
    out = run_subprocess(code, devices=8)
    assert "COMPRESSED OK" in out


@pytest.mark.slow
def test_elastic_restore_onto_different_mesh():
    code = '''
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import save_checkpoint, restore_checkpoint
tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((8,))}
with tempfile.TemporaryDirectory() as d:
    mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    t4 = jax.device_put(tree, NamedSharding(mesh4, P("data")))
    save_checkpoint(d, 7, t4)
    # restore onto an 8-way mesh (elastic scale-up)
    mesh8 = jax.make_mesh((8,), ("data",))
    sh8 = {"w": NamedSharding(mesh8, P("data")), "b": NamedSharding(mesh8, P())}
    got, step, _ = restore_checkpoint(d, tree, shardings=sh8)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
    assert got["w"].sharding.num_devices == 8 or got["w"].sharding.mesh.size == 8
print("ELASTIC OK")
'''
    out = run_subprocess(code, devices=8)
    assert "ELASTIC OK" in out


def test_cache_spec_fitting_drops_nondivisible_axes():
    """kv=1 head can't shard over model=16: _fit_spec must drop the axis
    (tested against a mock 16x16 mesh shape)."""
    from repro.dist.sharding import _fit_spec

    class MockMesh:
        shape = {"data": 16, "model": 16}

    # (L, B, S, kv=1, hd): model proposed on the kv dim -> dropped
    fitted = _fit_spec((None, "data", None, "model", None), (4, 32, 64, 1, 16), MockMesh())
    assert fitted[3] is None
    # divisible dims keep their axes
    fitted = _fit_spec((None, "data", None, "model", None), (4, 32, 64, 16, 16), MockMesh())
    assert fitted[3] == "model" and fitted[1] == "data"
