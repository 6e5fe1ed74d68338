"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required for the dry-run's
512-placeholder-device initialization order (launch/dryrun.py).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes the partitioner may shard freely (``Auto``).

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` and the implicitly sharded gathers of the
    model code are refused; every mesh of this repo is an ``Auto`` mesh.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh over however many local devices exist (tests/smoke)."""
    n = len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def place_serving_state(params, head, mesh):
    """Shard serving state onto ``mesh`` per ``sharding/rules.py``.

    The one placement path shared by the ``LM`` facade, the engine backend,
    and ``launch.serve.generate``: backbone params via ``params_shardings``,
    the head's frozen arrays (if any) via ``head_param_shardings``.  A
    no-op copy-wise when the arrays are already placed (``jax.device_put``
    short-circuits on matching shardings).

    Args:
      params: backbone parameter pytree.
      head: a ``repro.api`` LogitHead (its ``params`` may be ``None``).
      mesh: the target ``jax.sharding.Mesh``.

    Returns:
      ``(params, head)`` placed on the mesh.
    """
    from repro.sharding.rules import head_param_shardings, params_shardings

    params = jax.device_put(params, params_shardings(params, mesh))
    if head.params is not None:
        head = head.with_params(jax.device_put(
            head.params, head_param_shardings(head.params, mesh)))
    return params, head


def parse_mesh(spec):
    """A serving mesh from a ``"<data>x<model>"`` spec string.

    The CLI / API surface for sharded serving (``serve.py --mesh 4x2``,
    ``LM.from_config(mesh="4x2")``): builds a ``(data, model)`` mesh over
    the local devices.  Accepts an existing ``Mesh`` (returned unchanged)
    or ``None`` (returns ``None``) so callers can thread user input through
    without case analysis.

    Args:
      spec: ``None``, a ``jax.sharding.Mesh``, or a string like ``"4x2"``
        (data × model).

    Returns:
      A ``jax.sharding.Mesh`` with axes ``("data", "model")``, or ``None``.

    Raises:
      ValueError: on a malformed spec string or when the requested shape
        needs more devices than the process has (forced-CPU runs set
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    if spec is None or isinstance(spec, jax.sharding.Mesh):
        return spec
    try:
        data, model = (int(p) for p in str(spec).lower().split("x"))
    except ValueError:
        raise ValueError(
            f"mesh spec {spec!r} is not of the form '<data>x<model>' "
            f"(e.g. '4x2')") from None
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh {spec!r} needs {data * model} devices but only {n} "
            f"are visible; set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={data * model} for a forced-CPU mesh")
    return _auto_mesh((data, model), ("data", "model"))
