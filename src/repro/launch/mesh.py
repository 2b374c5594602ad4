"""Production mesh construction + logical-axis rule installation.

``make_production_mesh`` is a FUNCTION (never a module constant) so that
importing this module never touches jax device state — the dry-run must
set XLA_FLAGS before the first jax call.

Mesh semantics (DESIGN §5):
    single-pod  (16, 16)        axes ("data", "model")    = 256 chips
    multi-pod   (2, 16, 16)     axes ("pod", "data", "model") = 512 chips
"pod" is the outermost data-parallel axis (replica gradients cross DCN);
"data" is in-pod DP + FSDP; "model" is tensor/sequence/expert parallel.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import AxisType, Mesh

from repro.dist import sharding as sharding_lib


def make_mesh(shape, names) -> Mesh:
    """``jax.make_mesh`` with Auto axes. The logical-axis rules place
    arrays with ``with_sharding_constraint``, which refuses the Explicit
    axes ``jax.make_mesh`` builds by default."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    data = data if data is not None else n // model
    return make_mesh((data, model), ("data", "model"))


@contextlib.contextmanager
def activate(mesh: Mesh, cfg_arch=None, *, seq_parallel: bool = True):
    """Enter the mesh and install the matching logical-axis rules."""
    multi_pod = "pod" in mesh.shape
    kv_ok = bool(cfg_arch and cfg_arch.n_kv_heads
                 and cfg_arch.n_kv_heads % mesh.shape["model"] == 0)
    rules = sharding_lib.standard_rules(
        multi_pod=multi_pod,
        kv_shardable=kv_ok,
        moe_parallelism=(cfg_arch.moe_parallelism if cfg_arch else "tp"),
        seq_parallel=seq_parallel,
    )
    with mesh, sharding_lib.use_rules(rules, mesh):
        yield mesh
