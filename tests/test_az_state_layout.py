"""The layout contract of the learner's state (``train/az_trainer.py``
``held_layouts``, ``AzTrainer._hold_on`` and the pin in ``_step``): the
state is in the client's default layout at every program's boundary, so
anyone may build one; where the client holds a kernel's operand off
row-major the step runs that leaf's update in the client's layout. On the
CPU row-major is the only default, so ``tripped`` makes the client answer
as a TPU does for a width that is not whole lanes (minor dimensions
swapped): the pinned step then runs here, with a layout XLA's CPU backend
takes too. What the pin does to the compiled step is
``tests/test_trunk_tpu_compile.py``'s (a described v5e)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Layout

from fishnet_tpu.models.az import AzConfig
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.telemetry.spans import RECORDER
from fishnet_tpu.train import az_trainer
from fishnet_tpu.train.az_trainer import AzTrainer, AzTrainState, held_layouts
from fishnet_tpu.train.checkpoint import restore_checkpoint, save_checkpoint

B = 4
TOWER = AzConfig(channels=8, blocks=2, value_hidden=8)
GATED = TrunkConfig(hidden=32, heads=2, head_dim=16, layers=2, experts=4, experts_per_token=2, expert_width=16, value_hidden=8)
# the fourth block: ungated experts (no ``experts_gate``), a share of them held and balanced
UNGATED = TrunkConfig(hidden=32, heads=2, kv_heads=1, head_dim=16, qk_norm=False, pattern="ME*", experts=8, experts_per_token=2, expert_width=16,
                      gated_ffn=False, shared_width=8, value_hidden=8, mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=8,
                      router_score="sigmoid", route_norm=True, held_experts=(2, 4), balance_rate=0.001)
NETS = {"tower": TOWER, "gated": GATED, "ungated": UNGATED}
#: The kernels' operands in each net
OPERANDS = {"tower": (), "gated": ("experts_gate", "experts_up", "experts_down"), "ungated": ("experts_up", "experts_down")}


def batch_of(boards=B):
    rng = np.random.default_rng(7)
    target = rng.random((boards, 4672)).astype(np.float32)
    return {"planes": rng.normal(0, 1, (boards, 8, 8, 19)).astype(np.float32),
            "policy_target": target / target.sum(-1, keepdims=True),
            "value_target": rng.uniform(-1, 1, boards).astype(np.float32)}


def minor_dimensions_swapped(device, dtype, shape):
    """What a TPU's client answers for ``f32[3, 8, 2688, 1856]``: ``{2,3,1,0}``."""
    order = list(range(len(shape)))
    order[-2:] = order[-2:][::-1]
    return Layout(major_to_minor=tuple(order))


@pytest.fixture(params=[False, True], ids=["default", "tripped"])
def tripped(request, monkeypatch):
    """Whether the client holds a matrix column-major when left to itself."""
    if request.param:
        monkeypatch.setattr(az_trainer, "_client_default", minor_dimensions_swapped)
    return request.param


def same_arrays(want, got, what=""):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(jax.device_get(want))[0], jax.tree.leaves(jax.device_get(got)), strict=True):
        np.testing.assert_array_equal(a, b, err_msg=f"{what} {jax.tree_util.keystr(path)}")


# -- the rule ------------------------------------------------------------------------------


@pytest.mark.parametrize("net", NETS)
def test_the_step_follows_the_client_for_the_kernels_operands_it_holds_off_row_major(net, tripped):
    trainer = AzTrainer(NETS[net])
    params = jax.eval_shape(trainer._init, jax.random.PRNGKey(0)).params
    held = held_layouts(params, None, jax.devices()[0])
    assert held == trainer._held and set(held) == (set(OPERANDS[net]) if tripped else set())  # ``mamba_in``, the router, the shared experts: never
    assert all(layout == Layout(major_to_minor=(0, 1, 3, 2)) for layout in held.values())
    assert trainer._held_fields == {"layout_held_leaves": 3 * len(held),  # the weight and AdamW's two moments
                                    "layout_held_bytes": sum(3 * 4 * params[name].size for name in held)}


def test_the_cpu_client_answers_row_major():
    layout = az_trainer._client_default(jax.devices()[0], jnp.dtype("float32"), (3, 8, 2688, 1856))
    assert layout.major_to_minor == (0, 1, 2, 3)


@pytest.mark.parametrize("net", ["gated", "ungated"])
def test_the_pinned_update_is_the_same_mathematics(net, monkeypatch):
    """A layout moves no number but the last digit: AdamW is elementwise,
    and XLA vectorises the pinned fusion otherwise."""
    plain, batch = AzTrainer(NETS[net]), batch_of()
    monkeypatch.setattr(az_trainer, "_client_default", minor_dimensions_swapped)
    pinned = AzTrainer(NETS[net])
    assert not plain._held and set(pinned._held) == set(OPERANDS[net])
    a, b = plain.init(3), pinned.init(3)
    same_arrays(a, b, "init")
    for _ in range(3):
        a, b = plain.step(a, batch)[0], pinned.step(b, batch)[0]
    for name, value in jax.device_get(a.params).items():
        np.testing.assert_allclose(np.asarray(b.params[name]), value, rtol=1e-4, atol=1e-7, err_msg=name)
    assert b.params["experts_up"].format.layout.major_to_minor == (0, 1, 2, 3)  # at the boundary: the client's default, here row-major


# -- who may hand the trainer a state ----------------------------------------------------------


@pytest.mark.parametrize("net", NETS)
def test_a_state_built_from_host_arrays_steps_to_the_same_parameters(net, tripped):
    """What ``benchmark/families/*.state_from_params`` does: arrays made
    outside the trainer, in whatever layout the client gives them."""
    trainer, batch = AzTrainer(NETS[net]), batch_of()
    theirs = jax.device_get(trainer.init(3))  # numpy leaves: no layout at all
    rebuilt = AzTrainState({k: jnp.array(v) for k, v in theirs.params.items()}, jax.tree.map(jnp.array, theirs.opt_state),
                           jnp.zeros((), jnp.int32), {k: jnp.array(v) for k, v in theirs.buffers.items()})
    states = {"init": trainer.init(3), "host": theirs, "rebuilt": rebuilt}
    for _ in range(3):
        states = {how: trainer.step(state, batch)[0] for how, state in states.items()}
    ours = states.pop("init")
    for how, state in states.items():
        same_arrays(ours, state, how)


@pytest.mark.parametrize("net", NETS)
def test_export_and_the_checkpoint_round_trip_give_the_same_arrays(net, tripped, tmp_path):
    trainer, batch = AzTrainer(NETS[net]), batch_of()
    state = trainer.init(5)
    for _ in range(2):
        state, _ = trainer.step(state, batch)
    want = jax.device_get(state)
    trainer.export(state, str(tmp_path / "net.npz"))
    exported = np.load(tmp_path / "net.npz")
    for name, value in {**want.params, **want.buffers}.items():
        np.testing.assert_array_equal(exported[name], value, err_msg=name)
    save_checkpoint(tmp_path / "ckpt", state)
    restored = restore_checkpoint(tmp_path / "ckpt", trainer.init(0))
    same_arrays(want, restored, "restored")
    state, _ = trainer.step(state, batch)
    restored, _ = trainer.step(restored, batch)
    same_arrays(state.params, restored.params, "a step on")


@pytest.mark.parametrize("net", NETS)
def test_the_mesh_path_steps(net, tripped):
    from fishnet_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(devices[:8])
    data, model = mesh.devices.shape
    cfg = AzConfig(channels=8 * model, blocks=2, value_hidden=8) if net == "tower" else NETS[net]
    trainer = AzTrainer(cfg, mesh=mesh)
    assert set(trainer._held) == (set(OPERANDS[net]) if tripped else set())  # asked of the mesh's first device, for a shard's shape
    batch = batch_of(8 * data)
    state = trainer.init(1)
    placed = jax.tree.map(lambda held, host: jax.device_put(host, held.sharding), trainer.init(0), jax.device_get(state))  # as ``restore_checkpoint`` places one
    state, metrics = trainer.step(state, batch)
    again, _ = trainer.step(placed, batch)
    assert np.isfinite(float(metrics["loss"])) and int(state.step) == 1
    same_arrays(state.params, again.params, "placed")


# -- the counter the mechanism brings -----------------------------------------------------------


@pytest.mark.parametrize("net", NETS)
def test_the_init_span_counts_the_leaves_held_off_row_major_and_their_bytes(net, tripped):
    started = time.monotonic()
    trainer = AzTrainer(NETS[net])
    state = trainer.init(0)
    span = [s for s in RECORDER.spans() if s["t"] >= started and s["stage"] == "train_init"][-1]
    experts = [state.params[name] for name in OPERANDS[net]]
    assert span["trainer"] == "az" and span["layout_held_leaves"] == 3 * len(experts) * tripped
    assert span["layout_held_bytes"] == (3 * sum(value.nbytes for value in experts) if tripped else 0)
    assert {"compile_s", "cache_load_s", "trace_lower_s", "cache_misses"} <= set(span)  # what the span had is still there


def test_the_nnue_trainers_span_has_no_such_field():
    from fishnet_tpu.train.model import NetConfig
    from fishnet_tpu.train.trainer import Trainer

    started = time.monotonic()
    Trainer(NetConfig(num_features=64, max_active=4, l1=16, l2=4, l3=4, num_buckets=2, king_buckets=4)).init(0)
    span = [s for s in RECORDER.spans() if s["t"] >= started and s["stage"] == "train_init"][-1]
    assert span["trainer"] == "nnue" and "layout_held_leaves" not in span
