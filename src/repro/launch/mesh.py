"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run entry point (launch/dryrun.py) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before importing jax;
smoke tests and benchmarks see the single real CPU device.

Every mesh here has explicit Auto axis types: GSPMD places the sharding
annotations, which is what every caller wants.
"""
from __future__ import annotations

import warnings

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, *, strict: bool = False):
    """A (data, model) mesh over whatever devices exist (tests / examples /
    the serving engine's --mesh flag).

    When fewer devices exist than ``data * model`` the shape is clamped —
    historically *silently*, so ``--mesh 1,8`` on a 1-device host quietly
    served single-device with no TP at all. Now a degenerate clamp WARNS,
    and ``strict=True`` (the launcher's serving path) raises instead: an
    unsatisfiable mesh shape is an operator error, not a fallback.
    """
    n = len(jax.devices())
    data_eff = min(data, n)
    model_eff = min(model, max(1, n // data_eff))
    if (data_eff, model_eff) != (data, model):
        msg = (f"mesh shape ({data}, {model}) needs {data * model} devices "
               f"but only {n} exist; degenerating to "
               f"({data_eff}, {model_eff})")
        if strict:
            raise ValueError(msg)
        warnings.warn(msg, stacklevel=2)
    return _make_mesh((data_eff, model_eff), ("data", "model"))
