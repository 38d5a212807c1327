"""Production meshes (as functions — importing never touches device state)."""
import jax


def auto_mesh(shape, axes):
    """A GSPMD mesh: ``jax.make_mesh`` with every axis ``Auto`` (its default
    is ``Explicit``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests."""
    return auto_mesh((1, 1), ("data", "model"))
