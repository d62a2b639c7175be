"""launch.mesh: make_mesh / make_production_mesh / make_engine_meshes
under forced host device counts (subprocess)."""
import jax
import pytest

from repro.launch import mesh as mesh_mod
from tests.utils import check, run_with_devices


# -- axis types (in-process; single device is enough) ------------------------


def test_axis_kwargs_with_axistype():
    kw = mesh_mod._axis_kwargs(3)
    assert kw == {"axis_types": (mesh_mod.AxisType.Auto,) * 3}


# -- make_engine_meshes validation (in-process) ------------------------------


def test_engine_meshes_reject_bad_factors():
    with pytest.raises(ValueError, match=">= 1"):
        mesh_mod.make_engine_meshes(0, 1)
    with pytest.raises(ValueError, match=">= 1"):
        mesh_mod.make_engine_meshes(1, 2, 0)


def test_engine_meshes_reject_overflow():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        mesh_mod.make_engine_meshes(n + 1, 1)


# -- forced host device counts (subprocess) ----------------------------------


@pytest.mark.parametrize("n,shape,axes", [
    (2, (2,), ("model",)),
    (4, (2, 2), ("data", "model")),
    (8, (2, 4), ("data", "model")),
    (8, (2, 2, 2), ("data", "model", "expert")),
])
def test_make_mesh_forced_counts(n, shape, axes):
    res = run_with_devices(f"""
from repro.launch.mesh import make_mesh
m = make_mesh({shape!r}, {axes!r})
assert tuple(m.devices.shape) == {shape!r}, m.devices.shape
assert dict(m.shape) == dict(zip({axes!r}, {shape!r})), m.shape
print("OK")
""", n_devices=n)
    check(res)
    assert "OK" in res.stdout


def test_production_mesh_needs_a_full_pod():
    """make_production_mesh wants 16x16=256 devices; at 8 it must fail
    loudly (a mis-sized mesh silently wrapping devices would corrupt the
    sharding layout)."""
    res = run_with_devices("""
from repro.launch.mesh import make_production_mesh
try:
    make_production_mesh()
except ValueError as e:
    print("RAISED")
else:
    print("UNEXPECTED-OK")
""", n_devices=8)
    check(res)
    assert "RAISED" in res.stdout


def test_engine_meshes_partition_is_disjoint():
    """dp engine shards are disjoint device sets with data=1 per engine;
    leftover devices idle deliberately; overflow raises."""
    res = run_with_devices("""
from repro.launch.mesh import make_engine_meshes

ms = make_engine_meshes(2, 2)                      # 4 of 8 used, 4 idle
assert len(ms) == 2
ids = [set(d.id for d in m.devices.flat) for m in ms]
assert not (ids[0] & ids[1])
assert all(dict(m.shape) == {"data": 1, "model": 2} for m in ms)

mse = make_engine_meshes(2, 2, 2)                  # all 8, expert axis
ids = [set(d.id for d in m.devices.flat) for m in mse]
assert not (ids[0] & ids[1])
assert all(dict(m.shape) == {"data": 1, "model": 2, "expert": 2}
           for m in mse)

try:
    make_engine_meshes(3, 3)
except ValueError:
    print("OK")
else:
    print("UNEXPECTED-OK")
""", n_devices=8)
    check(res)
    assert "OK" in res.stdout and "UNEXPECTED" not in res.stdout
