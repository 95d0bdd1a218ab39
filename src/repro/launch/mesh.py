"""Production mesh definitions (assignment MULTI-POD DRY-RUN step 1).

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax call).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """A mesh whose axes are all ``Auto`` (GSPMD-propagated) sharding."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, worker: int = 1):
    """Small mesh over the actually-present devices (tests/examples).

    ``worker > 1`` prepends the serving "worker" axis (coded streams are
    worker-major over it, DESIGN.md §13); ``worker == 1`` keeps the exact
    pre-existing 2-axis ("data", "model") mesh so train paths are
    unchanged.
    """
    n = len(jax.devices())
    if worker * data * model > n:
        raise ValueError(f"mesh {worker}x{data}x{model} > {n} devices")
    if worker == 1:
        return _make_mesh((data, model), ("data", "model"))
    return _make_mesh((worker, data, model), ("worker", "data", "model"))


def make_worker_mesh(workers: int, model: int = 1):
    """Serving mesh: one rank per coded worker (× optional model axis).

    Each rank along "worker" owns a contiguous block of the N+1 coded
    streams (worker-major layout) — a straggling/Byzantine worker is an
    *actual device*, and the decode tail gathers only survivor shards.
    """
    n = len(jax.devices())
    if workers * model > n:
        raise ValueError(
            f"worker mesh {workers}x{model} needs {workers * model} devices, "
            f"have {n} (set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return _make_mesh((workers, model), ("worker", "model"))


def make_production_serving_mesh(*, workers: int = 16, model: int = 16,
                                 multi_pod: bool = False):
    """256-chip serving pod: 16 coded workers × 16-way tensor parallel.

    Multi-pod adds a leading "pod" axis (data-parallel pool replicas).
    """
    if multi_pod:
        return _make_mesh((2, workers, model), ("pod", "worker", "model"))
    return _make_mesh((workers, model), ("worker", "model"))


# TPU v5e hardware constants for the roofline (assignment §Roofline).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW_PER_LINK = 50e9          # bytes/s per link
