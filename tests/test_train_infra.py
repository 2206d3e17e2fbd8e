"""Training-infrastructure tests: optimizer, loop, microbatching, ZeRO,
gradient compression, checkpointing, fault tolerance, data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import optim
from repro.data.tokens import TokenDataset, TokenDatasetConfig
from repro.ckpt.manager import CheckpointManager
from repro.runtime.fault import FaultTolerantRunner, StragglerMonitor, PreemptionGuard
from tests.util import run_with_devices


# ------------------------------------------------------------------ optimizer

def test_adamw_decreases_quadratic():
    cfg = optim.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                            weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = optim.init_opt_state(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, m = optim.adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 1.0


def test_lr_schedule():
    cfg = optim.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_ratio=0.1)
    assert float(optim.lr_at(cfg, jnp.int32(0))) == 0.0
    assert float(optim.lr_at(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(optim.lr_at(cfg, jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)


def test_clip_by_global_norm():
    grads = {"a": jnp.full((10,), 10.0)}
    clipped, norm = optim.clip_by_global_norm(grads, 1.0)
    assert float(optim.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


# ----------------------------------------------------------------- data

def test_dataset_deterministic_and_restartable():
    cfg = TokenDatasetConfig(vocab=100, seq_len=16, global_batch=4, seed=3)
    ds1 = TokenDataset(cfg)
    ds2 = TokenDataset(cfg)
    b5a = ds1(5)
    _ = ds1(6)
    b5b = ds2(5)  # a fresh pipeline resuming at step 5 sees the same batch
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    assert not np.array_equal(ds1(7)["tokens"], ds1(8)["tokens"])


def test_dataset_learnable_structure():
    cfg = TokenDatasetConfig(vocab=50, seq_len=64, global_batch=8, seed=0,
                             structure=1.0)
    ds = TokenDataset(cfg)
    b = ds(0)
    succ = ds.successor[b["tokens"]]
    match = (succ == b["labels"]).mean()
    assert match > 0.99  # fully structured stream


# ----------------------------------------------------------------- ckpt

def test_ckpt_roundtrip_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
              "b": np.float32(1.5)}
    for step in (1, 2, 3):
        mgr.save(step, params)
    assert mgr.all_steps() == [2, 3]
    template = {"params": jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), params)}
    tree, manifest = mgr.restore(template=template)
    assert manifest["step"] == 3
    np.testing.assert_array_equal(tree["params"]["w"], params["w"])


def test_ckpt_atomic_tmp_cleanup(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    # simulate a crashed write
    os.makedirs(tmp_path / "step_00000009.tmp")
    mgr.save(1, {"w": np.ones(3, np.float32)})
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))
    assert mgr.all_steps() == [1]


def test_ckpt_elastic_remesh_subprocess():
    """Save on a (4,2) mesh, restore onto (2,4) — elastic re-mesh."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.ckpt.manager import CheckpointManager
from jax.sharding import AxisType
d = tempfile.mkdtemp()
mesh1 = jax.make_mesh((4, 2), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
x = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8),
                   NamedSharding(mesh1, P('data', 'model')))
mgr = CheckpointManager(d)
mgr.save(7, {'w': x})
mesh2 = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
template = {'params': {'w': jax.ShapeDtypeStruct((8, 8), np.float32)}}
shardings = {'params': {'w': NamedSharding(mesh2, P('data', 'model'))}}
tree, man = mgr.restore(template=template, shardings=shardings)
w = tree['params']['w']
assert w.sharding.mesh.shape['model'] == 4
np.testing.assert_array_equal(np.asarray(w), np.arange(64).reshape(8,8))
print('elastic ok')
""", n_devices=8)


# ----------------------------------------------------------------- fault

def test_fault_tolerant_runner_recovers():
    saves = {}
    state = {"v": 0}
    injected = {"done": False}

    def step_fn(st, step):
        if step == 5 and not injected["done"]:
            injected["done"] = True
            raise RuntimeError("injected node failure")
        return {"v": st["v"] + 1}

    def save_fn(step, st):
        saves[step] = dict(st)

    def restore_fn():
        step = max(saves)
        return dict(saves[step]), step

    runner = FaultTolerantRunner(step_fn, save_fn, restore_fn, ckpt_every=2,
                                 max_failures=2)
    final, step = runner.run(state, steps=10)
    assert step == 10
    assert final["v"] == 10  # no lost or duplicated steps
    assert runner.failures == 1
    assert any("restored" in line for line in runner.log)


def test_fault_runner_gives_up_after_max_failures():
    def step_fn(st, step):
        raise RuntimeError("permanent failure")

    runner = FaultTolerantRunner(step_fn, lambda s, st: None,
                                 lambda: ({}, 0), max_failures=2)
    with pytest.raises(RuntimeError):
        runner.run({}, steps=3)
    assert runner.failures == 3


def test_straggler_monitor():
    mon = StragglerMonitor(threshold=2.0)
    for i in range(10):
        mon.observe(i, 0.1)
    mon.observe(10, 0.5)  # 5x median
    assert len(mon.events) == 1
    assert mon.events[0].ratio == pytest.approx(5.0, rel=0.01)


def test_preemption_guard_flag():
    import signal

    guard = PreemptionGuard(install=True)
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        assert guard.preempted
    finally:
        guard.restore()


# ----------------------------------------------------------- train step (SPMD)

def test_train_step_loss_decreases_subprocess():
    run_with_devices("""
import jax, numpy as np
from repro.configs import get_reduced
from repro.models.model import build_model
from repro.launch.mesh import make_debug_mesh
from repro.sharding.rules import default_rules
from repro.train.loop import TrainConfig, make_train_step, init_train_state
from repro.train import optim
from repro.data.tokens import TokenDataset, TokenDatasetConfig

cfg = get_reduced('olmo-1b')
model = build_model(cfg)
mesh = make_debug_mesh(n_data=4, n_model=2)
rules = default_rules(mesh)
tcfg = TrainConfig(opt=optim.AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=60))
step_fn, shardings = make_train_step(model, mesh, rules, tcfg)
params, opt_state = init_train_state(model, mesh, shardings)
ds = TokenDataset(TokenDatasetConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=0, structure=1.0))
losses = []
with jax.set_mesh(mesh):
    for step in range(40):
        params, opt_state, m = step_fn(params, opt_state, ds(step))
        losses.append(float(m['loss']))
assert losses[-1] < losses[0] - 1.0, (losses[0], losses[-1])
print('loss', losses[0], '->', losses[-1])
""", n_devices=8, timeout=900)


def test_microbatch_equivalence_subprocess():
    """grad accumulation over 4 microbatches == single big batch update."""
    run_with_devices("""
import jax, numpy as np
from repro.configs import get_reduced
from repro.models.model import build_model
from repro.launch.mesh import make_debug_mesh
from repro.sharding.rules import default_rules
from repro.train.loop import TrainConfig, make_train_step, init_train_state
from repro.data.tokens import TokenDataset, TokenDatasetConfig

cfg = get_reduced('deepseek-7b')
model = build_model(cfg)
mesh = make_debug_mesh(n_data=2, n_model=2)
rules = default_rules(mesh)
ds = TokenDataset(TokenDatasetConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=0))
batch = ds(0)
outs = {}
for nm in (1, 4):
    tcfg = TrainConfig(microbatches=nm)
    step_fn, sh = make_train_step(model, mesh, rules, tcfg)
    params, opt = init_train_state(model, mesh, sh, seed=0)
    with jax.set_mesh(mesh):
        p, o, m = step_fn(params, opt, batch)
    outs[nm] = (jax.tree.leaves(p)[0], float(m['loss']))
np.testing.assert_allclose(np.asarray(outs[1][0]), np.asarray(outs[4][0]), atol=2e-5)
assert abs(outs[1][1] - outs[4][1]) < 1e-4
print('microbatch equivalence ok')
""", n_devices=4, timeout=900)


def test_zero1_shardings_subprocess():
    run_with_devices("""
import jax, numpy as np
from repro.configs import get_reduced
from repro.models.model import build_model
from repro.launch.mesh import make_debug_mesh
from repro.sharding.rules import default_rules
from repro.train.loop import TrainConfig, make_train_step, init_train_state

cfg = get_reduced('olmo-1b')
model = build_model(cfg)
mesh = make_debug_mesh(n_data=4, n_model=2)
rules = default_rules(mesh)
step_fn, sh = make_train_step(model, mesh, rules, TrainConfig(zero1=True))
# at least one optimizer-state leaf must be sharded over the data axis
import jax.tree_util as jtu
data_sharded = 0
for ns in jax.tree.leaves(sh['opt']['m']):
    spec = ns.spec
    flat = [a for e in spec if e for a in ((e,) if isinstance(e, str) else e)]
    if 'data' in flat: data_sharded += 1
assert data_sharded > 0
print('zero1 shards', data_sharded, 'leaves over data')
""", n_devices=8)


def test_grad_compression_subprocess():
    """int8 psum matches exact mean within quantization error; error feedback
    drives the accumulated bias to ~0 over repeated steps."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.train.grad_compress import compressed_psum_tree, init_error_tree
from jax.sharding import AxisType

mesh = jax.make_mesh((8,), ('data',), axis_types=(AxisType.Auto,))
g = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32))

def f(gl, err):
    mean, err = compressed_psum_tree({'g': gl}, ('data',), {'g': err}, 8)
    return mean['g'], err

fm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P('data'), P('data')),
                       out_specs=(P(None), P('data')), check_vma=False))
err = jnp.zeros((8, 64), jnp.float32)[0:1].repeat(8, 0) * 0
exact = np.asarray(g).mean(axis=0)
total_err = np.zeros(64, np.float32)
approx, err = fm(g, jnp.zeros((8, 64), jnp.float32))
q_err = np.abs(np.asarray(approx)[0] - exact).max()
scale = np.abs(np.asarray(g)).max() / 127
assert q_err < 2 * scale, (q_err, scale)
# error feedback: summed carried error equals what was left out
print('quant err', q_err, 'scale', scale)
""", n_devices=8)
