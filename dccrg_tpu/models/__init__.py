"""Application layer: the reference's examples/tests solvers as JAX
programs on top of the grid (SURVEY.md section L6) — and the model
zoo's registry surface.

Importing this package registers every zoo kernel with the fleet
(``"mhd"``, ``"vlasov"`` — schemas, seeded default inits and
conservation invariants included), so ``python -m dccrg_tpu.fleet``
job files and :class:`~dccrg_tpu.fleet.FleetJob` constructions can
name any zoo kernel without further setup; the fleet layer lazy-
imports this package on an unknown kernel name for the same effect.
The classic solver classes (``GridAdvection``, ``AdvectionSolver``,
``PoissonSolver``, ``GridMHD``, ``GridVlasov``, ...) stay LAZY — the
heavier submodules only import when an attribute is first touched.
"""

from __future__ import annotations

from . import mhd, vlasov

# kernel registration happens at package import (the zoo contract the
# fleet CLI depends on); both calls are idempotent
mhd.register()
vlasov.register()

#: the zoo table: fleet-kernel name -> structured info (fields,
#: conserved quantities, the model class name)
MODEL_ZOO = {
    "mhd": dict(mhd.ZOO_INFO),
    "vlasov": dict(vlasov.ZOO_INFO),
    "diffuse": {
        "kernel": "diffuse", "fields": ("rho",),
        "conserved": ("rho",),
        "model": None,
        "description": "neighbor-coupling relaxation (fleet workhorse)",
    },
    "advect_x": {
        "kernel": "advect_x", "fields": ("rho",),
        "conserved": ("rho",),
        "model": None,
        "description": "first-order upwind advection along +x",
    },
}

# attribute -> (submodule, attr) for the lazy classic solvers
_LAZY = {
    "AdvectionSolver": ("advection", "AdvectionSolver"),
    "GridAdvection": ("advection", "GridAdvection"),
    "PallasRotationAdvection": ("advection", "PallasRotationAdvection"),
    "PoissonSolver": ("poisson", "PoissonSolver"),
    "DensePoissonSolver": ("poisson", "DensePoissonSolver"),
    "GridMHD": ("mhd", "GridMHD"),
    "GridVlasov": ("vlasov", "GridVlasov"),
}


def __getattr__(name):
    entry = _LAZY.get(name)
    if entry is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    import importlib

    mod = importlib.import_module(f".{entry[0]}", __name__)
    val = getattr(mod, entry[1])
    globals()[name] = val
    return val


def ensure_registered() -> None:
    """Idempotent zoo registration hook (registration already ran at
    package import; this is the explicit spelling for lazy callers)."""
    mhd.register()
    vlasov.register()


def available_models() -> list:
    """The zoo, one dict per registered kernel: ``name``, ``fields``,
    ``conserved`` fields, the multi-device ``model`` class name and a
    one-line description — the README model table's source of truth."""
    return [dict(info, name=name)
            for name, info in sorted(MODEL_ZOO.items())]
