"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization; tests and
benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (pjit-style sharding propagation)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 v5e chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, *, skip: bool = False,
                   degrade: bool = False):
    """Small host-device mesh for integration tests.

    Needs ``data * model`` addressable devices (force with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
    initializes).  When fewer exist:
      * default       — raise with the XLA_FLAGS hint (no silent surprises);
      * ``skip=True``    — ``pytest.skip`` (the shared guard for mesh tests,
        so every test file stops hand-rolling its own device-count check);
      * ``degrade=True`` — halve axes toward (1, 1) until the mesh fits,
        so opportunistic callers (benches) still get *a* mesh.
    """
    have = len(jax.devices())
    if data * model > have:
        msg = (f"mesh ({data}, {model}) needs {data * model} devices, "
               f"have {have}")
        if jax.default_backend() == "cpu":
            # only sensible advice on CPU — on an accelerator host forcing
            # host-platform devices would silently serve on CPU instead
            msg += (f"; set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={data * model} "
                    f"before jax initializes")
        if skip:
            import pytest
            pytest.skip(msg)
        if not degrade:
            raise RuntimeError(msg)
        while data * model > have and model > 1:
            model = (model + 1) // 2
        while data * model > have and data > 1:
            data = (data + 1) // 2
    return _make_mesh((data, model), ("data", "model"))


def single_device_mesh():
    """(1, 1) ("data", "model") mesh over the default device.

    The serving engine's fallback: with it, the mesh-sharded window is the
    ONLY code path — single-device serving is just the degenerate mesh,
    not a separate branch."""
    return _make_mesh((1, 1), ("data", "model"))


def mesh_from_spec(spec: str | None):
    """Parse a ``--mesh`` CLI spec ("DATA,MODEL" or "DATAxMODEL", e.g.
    "2,4" or "2x4") into a ("data", "model") mesh; None -> the
    single-device fallback."""
    if spec is None:
        return single_device_mesh()
    try:
        data, model = (int(p) for p in spec.replace("x", ",").split(","))
    except ValueError:
        raise ValueError(f"--mesh expects DATA,MODEL (e.g. 2,4), got "
                         f"{spec!r}") from None
    return make_test_mesh(data, model)
