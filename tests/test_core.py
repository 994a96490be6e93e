"""Core runtime tests: context/mesh bootstrap, config, checkpoint round-trip."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.core import (MeshConfig, OrcaContext, ZooConfig,
                                    checkpoint, get_mesh, init_orca_context,
                                    make_mesh, stop_orca_context)


def test_init_local_context_default_mesh():
    mesh = init_orca_context("local")
    assert mesh.devices.size == 8  # conftest forces 8 CPU devices
    assert mesh.axis_names == ("data",)
    assert OrcaContext.initialized
    assert OrcaContext.mesh is mesh


def test_init_twice_reuses():
    m1 = init_orca_context("local")
    m2 = init_orca_context("local")
    assert m1 is m2


def test_mesh_shape_axes():
    mesh = init_orca_context("local", mesh_shape={"data": 2, "model": 4})
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "data": 2, "model": 4}


def test_mesh_auto_axis():
    mesh = make_mesh({"data": 0, "model": 2})
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "data": 4, "model": 2}


def test_mesh_bad_shape_raises():
    with pytest.raises(ValueError):
        make_mesh({"data": 16})  # more than the 8 available
    with pytest.raises(ValueError):
        make_mesh({"data": 0, "model": 3})  # 3 does not divide 8
    with pytest.raises(ValueError):
        MeshConfig(data=0, model=0).resolved(8)  # two wildcards


def test_mesh_subset_of_devices():
    mesh = make_mesh({"data": 2})  # debugging subset on an 8-device host
    assert mesh.devices.size == 2


def test_get_mesh_autoinit():
    mesh = get_mesh()
    assert mesh.devices.size == 8


def test_psum_on_mesh():
    """Real collective on the virtual mesh — the backbone of data parallelism."""
    mesh = init_orca_context("local")
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))

    def f(v):
        return jax.lax.psum(v.sum(), "data")

    from jax import shard_map
    out = jax.jit(
        shard_map(f, mesh=mesh, in_specs=P("data", None), out_specs=P())
    )(xs)
    assert float(out) == x.sum()


def test_config_from_dict_and_extra():
    cfg = ZooConfig.from_dict({
        "cluster_mode": "local",
        "mesh": {"data": 2, "model": 4},
        "custom_knob": 42,
    })
    assert cfg.mesh.model == 4
    assert cfg.extra["custom_knob"] == 42


def test_config_yaml_fallback(tmp_path):
    p = tmp_path / "conf.yaml"
    p.write_text("cluster_mode: local\nmesh:\n  data: 2\n  model: 4\n"
                 "pandas_read_backend: pandas\nremat: true\n")
    cfg = ZooConfig.from_file(str(p))
    assert cfg.mesh.model == 4
    assert cfg.remat is True


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "params": {"dense": {"w": np.ones((3, 4), np.float32),
                             "b": np.zeros((4,), np.float32)}},
        "step": 7,
        "lr": 0.1,
        "name": "m",
        "flags": (True, None),
        "history": [np.arange(5), 2.5],
    }
    path = checkpoint.save(str(tmp_path / "ckpt"), tree, step=7)
    back = checkpoint.restore(path)
    assert back["step"] == 7 and back["lr"] == 0.1 and back["name"] == "m"
    assert back["flags"] == (True, None)
    np.testing.assert_array_equal(back["params"]["dense"]["w"], tree["params"]["dense"]["w"])
    np.testing.assert_array_equal(back["history"][0], np.arange(5))
    assert checkpoint.latest_step(path) == 7
    assert checkpoint.exists(path)


def test_checkpoint_jax_arrays(tmp_path):
    tree = {"w": jnp.ones((2, 2)) * 3}
    path = checkpoint.save(str(tmp_path / "c"), tree)
    back = checkpoint.restore(path)
    np.testing.assert_array_equal(back["w"], np.ones((2, 2)) * 3)


def test_checkpoint_bfloat16_roundtrip(tmp_path):
    # npz alone degrades ml_dtypes to raw void; the uint-view encoding must
    # bring back real bfloat16 (the TPU-default training dtype)
    tree = {"w": jnp.asarray([[1.5, -2.0], [0.25, 3.0]], jnp.bfloat16),
            "f8": jnp.asarray([1.0, 0.5], jnp.float8_e4m3fn)}
    path = checkpoint.save(str(tmp_path / "c"), tree)
    back = checkpoint.restore(path)
    assert back["w"].dtype == jnp.bfloat16
    assert back["f8"].dtype == jnp.float8_e4m3fn
    np.testing.assert_array_equal(np.asarray(back["w"], np.float32),
                                  [[1.5, -2.0], [0.25, 3.0]])


def test_checkpoint_repeated_save_gc(tmp_path):
    d = str(tmp_path / "c")
    for k in range(3):
        checkpoint.save(d, {"w": np.full((2,), k, np.float32)}, step=k)
    back = checkpoint.restore(d)
    assert back["w"][0] == 2 and checkpoint.latest_step(d) == 2
    import os
    npzs = [n for n in os.listdir(d) if n.endswith(".npz")]
    assert len(npzs) == 1, npzs  # stale generations garbage-collected


def test_summary_writer(tmp_path):
    from analytics_zoo_tpu.core import SummaryWriter
    w = SummaryWriter(str(tmp_path), "train")
    for i in range(3):
        w.add_scalar("loss", 1.0 / (i + 1), i)
    w.close()
    scalars = SummaryWriter(str(tmp_path), "train").read_scalar("loss")
    assert [s for s, _ in scalars] == [0, 1, 2]
    assert scalars[0][1] == 1.0


# -- compile-cache placement (core/context.configure_compile_cache) -----------

_CACHE_PROBE = (
    "import jax; "
    "from analytics_zoo_tpu.core import init_orca_context; "
    "from analytics_zoo_tpu.serving import enable_aot_cache; "
    "init_orca_context('local'); print(jax.config.jax_compilation_cache_dir); "
    "print(enable_aot_cache('x')); "
    "print(jax.config.jax_compilation_cache_dir)")


@pytest.mark.parametrize("env_dir", ["/some/dir", None])
def test_compile_cache_placement(env_dir):
    """JAX_COMPILATION_CACHE_DIR set: the directory is the environment's and
    no call in the program moves it.  Unset: init_orca_context places the
    cache at the fixed <checkout>/.jax_cache, derived from the package's
    location — never a temporary directory, which would never hit."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd="/", capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    at_init, returned, after = out.stdout.split()
    if env_dir:
        assert at_init == returned == after == env_dir
    else:
        assert at_init == os.path.join(repo, ".jax_cache")
        assert returned == after == "x"  # a deployment placing its own
