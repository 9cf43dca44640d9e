"""The system's sharded entry and its mesh, beside `program.py`.

`program.py` holds the one-chip entries; this module adds the likelihood
that `core/distributed.py` shards over a ("data", "model") mesh, and the
helpers that build and install that mesh.  A checkout that holds the
benchmark alone fails here, before any result is printed.  Tests replace
the entries below to plant faults.
"""

from __future__ import annotations

from . import program  # noqa: F401  (puts the program's `src/` on the path)

from repro.core.distributed import geostat_loglik_distributed  # noqa: E402
from repro.launch.mesh import make_geostat_mesh, make_smoke_mesh  # noqa: E402
from repro.models.sharding import set_activation_mesh  # noqa: E402

__all__ = ["geostat_loglik_distributed", "make_geostat_mesh",
           "make_smoke_mesh", "set_activation_mesh"]
