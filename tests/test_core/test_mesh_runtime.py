"""Core substrate tests on the virtual 8-device CPU platform."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.core import (
    AXIS_NAMES,
    DATA_AXIS,
    MODEL_AXIS,
    Runtime,
    build_mesh,
    get_single_device_runtime,
    local_batch_size,
    resolve_precision,
    shard_batch,
)


def test_virtual_devices_present():
    assert len(jax.devices()) == 8


def test_build_mesh_shapes():
    mesh = build_mesh()
    assert mesh.shape[DATA_AXIS] == 8
    mesh2 = build_mesh(model_axis_size=2)
    assert mesh2.shape[DATA_AXIS] == 4
    assert mesh2.shape[MODEL_AXIS] == 2
    with pytest.raises(ValueError):
        build_mesh(model_axis_size=3)


def test_mesh_axis_names_match_the_canonical_vocabulary():
    """AXIS_NAMES is the single spelling authority (graftlint GL014 enforces
    it statically; build_mesh asserts it at runtime)."""
    assert AXIS_NAMES == (DATA_AXIS, MODEL_AXIS) == ("data", "model")
    assert tuple(build_mesh().axis_names) == AXIS_NAMES


def test_shard_batch_places_shards():
    mesh = build_mesh()
    batch = {"obs": np.arange(16 * 3, dtype=np.float32).reshape(16, 3)}
    sharded = shard_batch(batch, mesh)
    assert sharded["obs"].shape == (16, 3)
    assert len(sharded["obs"].addressable_shards) == 8
    assert sharded["obs"].addressable_shards[0].data.shape == (2, 3)
    np.testing.assert_array_equal(np.asarray(sharded["obs"]), batch["obs"])


def test_psum_over_mesh():
    mesh = build_mesh()
    x = shard_batch(np.ones((8, 4), np.float32), mesh)

    @jax.jit
    def total(v):
        return jnp.sum(v)

    assert float(total(x)) == 32.0


def test_runtime_launch_and_world():
    rt = Runtime(devices="auto", accelerator="cpu", precision="bf16-mixed").launch()
    assert rt.world_size == 8
    assert rt.is_global_zero
    assert rt.precision.compute_dtype == jnp.bfloat16
    assert rt.precision.param_dtype == jnp.float32
    key = rt.seed_everything(3)
    assert key is not None
    assert rt.local_batch_size(64) == 8
    single = get_single_device_runtime(rt)
    assert single.world_size == 1
    assert single.seed == 3


def test_runtime_device_count_limit():
    rt = Runtime(devices=2, accelerator="cpu").launch()
    assert rt.world_size == 2
    with pytest.raises(RuntimeError):
        Runtime(devices=99, accelerator="cpu").launch()


def test_precision_unknown():
    with pytest.raises(ValueError):
        resolve_precision("8-bit")


def test_local_batch_not_divisible():
    mesh = build_mesh()
    with pytest.raises(ValueError):
        local_batch_size(12, mesh)


def test_split_player_trainer_composes_with_model_axis():
    """Decoupled x TP (round-2 weak item 6, now supported): the trainer
    partition keeps the model axis — grid[0,0] plays, rows 1..d-1 train."""
    from sheeprl_tpu.core.mesh import DATA_AXIS, MODEL_AXIS, build_mesh, split_player_trainer

    mesh = build_mesh(model_axis_size=2)  # 4 x 2 on the 8-device CPU mesh
    player, trainer_mesh = split_player_trainer(mesh, "mesh")
    assert player == mesh.devices.reshape(4, 2)[0, 0]
    assert int(trainer_mesh.shape[DATA_AXIS]) == 3
    assert int(trainer_mesh.shape[MODEL_AXIS]) == 2
    assert player not in set(trainer_mesh.devices.flat)


def test_split_player_trainer_model_axis_needs_two_data_rows():
    import pytest

    from sheeprl_tpu.core.mesh import build_mesh, split_player_trainer

    mesh = build_mesh(devices=None, data_axis_size=1, model_axis_size=2)
    with pytest.raises(RuntimeError, match="2 data rows"):
        split_player_trainer(mesh, "mesh")


def test_split_player_trainer_auto_with_params():
    """auto + params threads the size guard (a round-2 review item): on the CPU test
    platform host==mesh silicon, so the split stays on-mesh regardless."""
    import jax.numpy as jnp

    from sheeprl_tpu.core.mesh import build_mesh, split_player_trainer

    mesh = build_mesh()
    player, trainer_mesh = split_player_trainer(
        mesh, "auto", params={"w": jnp.zeros((8, 8))}
    )
    assert player is not None and trainer_mesh is not None


def test_shard_batch_divisibility_error_names_axis_and_nearest():
    """shard_batch must refuse an indivisible batch with a diagnosable
    message: the axis name, its size, and the nearest valid batch sizes."""
    mesh = build_mesh()
    with pytest.raises(ValueError, match=r"`data` mesh axis \(size 8\)") as excinfo:
        shard_batch(np.ones((12, 3), np.float32), mesh)
    assert "8 or 16" in str(excinfo.value)


def test_shard_batch_divisibility_nearest_rounds_up_from_tiny_batch():
    mesh = build_mesh()
    with pytest.raises(ValueError, match="nearest valid batch size: 8"):
        shard_batch(np.ones((5, 3), np.float32), mesh)


def test_partition_plan_default_specs_and_data_size():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sheeprl_tpu.core.mesh import default_partition_plan

    mesh = build_mesh()
    plan = default_partition_plan(mesh)
    assert plan.data_size == 8
    assert plan.spec("batch") == P(DATA_AXIS)
    assert plan.spec("unregistered") == P()
    sh = plan.sharding("batch")
    assert isinstance(sh, NamedSharding) and sh.spec == P(DATA_AXIS)
    assert plan.replicated().spec == P()
    # User specs merge over (and can override) the default batch spec.
    plan2 = default_partition_plan(mesh, batch_specs={"rollout": P(None, DATA_AXIS)})
    assert plan2.spec("rollout") == P(None, DATA_AXIS)
    assert plan2.spec("batch") == P(DATA_AXIS)


def test_param_partition_spec_wide_rule():
    from jax.sharding import PartitionSpec as P

    from sheeprl_tpu.core.mesh import param_partition_spec

    mesh = build_mesh()  # model axis 1: everything replicated
    assert param_partition_spec(jnp.zeros((4, 2048)), mesh) == P()
    mesh2 = build_mesh(model_axis_size=2)
    # Wide float matrices split their last dim over `model`.
    assert param_partition_spec(jnp.zeros((4, 2048)), mesh2) == P(None, MODEL_AXIS)
    # Narrow, integer, or indivisible leaves stay replicated.
    assert param_partition_spec(jnp.zeros((4, 10)), mesh2) == P()
    assert param_partition_spec(jnp.zeros((2048,), jnp.int32), mesh2) == P()


def test_tree_shardings_mirrors_placement():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sheeprl_tpu.core.mesh import tree_shardings

    mesh = build_mesh()
    placed = jax.device_put(jnp.zeros((16, 4)), NamedSharding(mesh, P(DATA_AXIS)))
    tree = {"a": placed}
    shardings = tree_shardings(tree)
    assert shardings["a"].spec == P(DATA_AXIS)
